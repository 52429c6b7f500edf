"""Frozen-BERT GraphDef builder (real TensorFlow as oracle).

The reference validates its import axis by running a real frozen
BERT-MRPC graph through the TF importer and fine-tuning it
(`/root/reference/nd4j/nd4j-backends/nd4j-tests/src/test/java/org/nd4j/imports/TFGraphs/BERTGraphTest.java:29`).
This image has no egress, so instead of downloading the Google
checkpoint we *generate* a BERT graph of any size with in-process
TensorFlow (the same dependency the reference's `nd4j-tensorflow`
GraphRunner uses), freeze it, and keep TF's own outputs as the golden
expectations. Architecture matches the BERT encoder stack: learned
token/position/segment embeddings, post-LN transformer blocks with
erf-GELU, tanh pooler over [CLS], classifier head.

Used by tests/fixtures/gen_tfgraphs.py (corpus case `bert_mini`) and
the BERT fine-tune test.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def bert_config(preset: str = "mini") -> Dict[str, int]:
    """Named sizes. `base` mirrors google-research BERT-base (L=12,
    H=768, A=12); `mini`/`tiny` are the small grid sizes from the
    public BERT-miniatures release."""
    presets = {
        "tiny": dict(L=2, H=128, A=2),
        "mini": dict(L=4, H=256, A=4),
        "small": dict(L=4, H=512, A=8),
        "medium": dict(L=8, H=512, A=8),
        "base": dict(L=12, H=768, A=12),
    }
    return dict(presets[preset])


def build_frozen_bert(vocab: int = 1000, seq_len: int = 128,
                      n_classes: int = 2, preset: str = "mini",
                      seed: int = 0) -> Tuple[bytes, dict]:
    """Build + freeze a BERT classifier graph with real TF.

    Returns (graphdef_bytes, meta) where meta has input placeholder
    names ('ids', 'mask'), the output node name, and sizes. Outputs are
    class probabilities [batch, n_classes].
    """
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)

    cfg = bert_config(preset)
    L, H, A = cfg["L"], cfg["H"], cfg["A"]
    T, V = seq_len, vocab
    rs = np.random.RandomState(seed)

    def W(*shape, s=0.02):
        return tf.constant(rs.randn(*shape).astype(np.float32) * s)

    p: Dict[str, object] = {
        "tok_emb": W(V, H), "pos_emb": W(T, H), "seg_emb": W(2, H),
        "emb_ln_g": tf.constant(np.ones(H, np.float32)),
        "emb_ln_b": tf.constant(np.zeros(H, np.float32)),
        "pool_w": W(H, H), "pool_b": W(H),
        "cls_w": W(H, n_classes), "cls_b": W(n_classes),
    }
    for l in range(L):
        for n in ("q", "k", "v", "o"):
            p[f"l{l}_{n}_w"] = W(H, H)
            p[f"l{l}_{n}_b"] = W(H)
        p[f"l{l}_ff1_w"] = W(H, 4 * H)
        p[f"l{l}_ff1_b"] = W(4 * H)
        p[f"l{l}_ff2_w"] = W(4 * H, H)
        p[f"l{l}_ff2_b"] = W(H)
        for ln in ("ln1", "ln2"):
            p[f"l{l}_{ln}_g"] = tf.constant(np.ones(H, np.float32))
            p[f"l{l}_{ln}_b"] = tf.constant(np.zeros(H, np.float32))

    def layer_norm(x, g, b):
        mean = tf.reduce_mean(x, axis=-1, keepdims=True)
        var = tf.reduce_mean(tf.math.squared_difference(x, mean),
                             axis=-1, keepdims=True)
        return (x - mean) * tf.math.rsqrt(var + 1e-12) * g + b

    @tf.function
    def bert(ids, mask):
        x = (tf.gather(p["tok_emb"], ids) + p["pos_emb"]
             + tf.gather(p["seg_emb"], tf.zeros_like(ids)))
        x = layer_norm(x, p["emb_ln_g"], p["emb_ln_b"])
        amask = (1.0 - tf.cast(mask, tf.float32)[:, None, None, :]) * -1e4
        for l in range(L):
            q = tf.matmul(x, p[f"l{l}_q_w"]) + p[f"l{l}_q_b"]
            k = tf.matmul(x, p[f"l{l}_k_w"]) + p[f"l{l}_k_b"]
            v = tf.matmul(x, p[f"l{l}_v_w"]) + p[f"l{l}_v_b"]

            def heads(t):
                t = tf.reshape(t, [-1, T, A, H // A])
                return tf.transpose(t, [0, 2, 1, 3])

            scores = tf.matmul(heads(q), heads(k), transpose_b=True) \
                / np.float32(np.sqrt(H // A))
            probs = tf.nn.softmax(scores + amask, axis=-1)
            ctx = tf.transpose(tf.matmul(probs, heads(v)), [0, 2, 1, 3])
            ctx = tf.reshape(ctx, [-1, T, H])
            att = tf.matmul(ctx, p[f"l{l}_o_w"]) + p[f"l{l}_o_b"]
            x = layer_norm(x + att, p[f"l{l}_ln1_g"], p[f"l{l}_ln1_b"])
            h = tf.nn.gelu(tf.matmul(x, p[f"l{l}_ff1_w"])
                           + p[f"l{l}_ff1_b"], approximate=False)
            h = tf.matmul(h, p[f"l{l}_ff2_w"]) + p[f"l{l}_ff2_b"]
            x = layer_norm(x + h, p[f"l{l}_ln2_g"], p[f"l{l}_ln2_b"])
        cls = tf.gather(x, 0, axis=1)
        pooled = tf.tanh(tf.matmul(cls, p["pool_w"]) + p["pool_b"])
        return tf.nn.softmax(tf.matmul(pooled, p["cls_w"]) + p["cls_b"])

    cf = bert.get_concrete_function(
        tf.TensorSpec([None, T], tf.int32, name="ids"),
        tf.TensorSpec([None, T], tf.int32, name="mask"))
    frozen = convert_variables_to_constants_v2(cf)
    gd = frozen.graph.as_graph_def()
    out_node = gd.node[-1].name
    meta = {"inputs": ["ids", "mask"], "output": out_node,
            "seq_len": T, "vocab": V, "n_classes": n_classes, **cfg}
    return gd.SerializeToString(), meta


def reference_outputs(graph_bytes: bytes, feeds: Dict[str, np.ndarray],
                      out_node: str) -> np.ndarray:
    """Run the frozen graph with real TF (the oracle)."""
    import tensorflow as tf
    gd = tf.compat.v1.GraphDef()
    gd.ParseFromString(graph_bytes)

    def run(ids, mask):
        out, = tf.graph_util.import_graph_def(
            gd, input_map={"ids": ids, "mask": mask},
            return_elements=[f"{out_node}:0"])
        return out

    fn = tf.compat.v1.wrap_function(
        run, [tf.TensorSpec(feeds["ids"].shape, tf.int32),
              tf.TensorSpec(feeds["mask"].shape, tf.int32)])
    return fn(tf.constant(feeds["ids"]),
              tf.constant(feeds["mask"])).numpy()
