"""The program's LFM2-MoE-shaped class under the benchmark's weights
(``configs/*.json`` with ``"served": "lfm2_moe"``).

``build`` constructs ``zoo.lfm2_moe.Lfm2MoeLM`` from the
configuration's ``model`` block (the published ``config.json`` keys as
this chip holds them, and ``dtype``) and puts the reference module's
weights in place. The reference makes them on the device, a layer a
call, already rounded to the configuration's dtype; the program keeps
its matrices in that dtype and what it computes in float32 (norm
weights, the convolution's taps, the router) as float32 holding the
same rounded values. The names are the same on both sides.
"""
from __future__ import annotations

FLOAT32 = ("operator_norm", "ffn_norm", "q_norm", "k_norm", "conv_w",
           "W_g", "expert_bias")


def program_params(emb: dict, layers: list) -> dict:
    import jax.numpy as jnp
    return {"embed": emb["embed"],
            "embedding_norm": emb["embedding_norm"].astype(jnp.float32),
            "layers": [{k: (v.astype(jnp.float32) if k in FLOAT32 else v)
                        for k, v in w.items()} for w in layers]}


def build(config: dict, seed: int, reference):
    """The model ``register_generator`` is handed."""
    import jax
    from deeplearning4j_tpu.zoo.lfm2_moe import Lfm2MoeLM

    m = config["model"]
    lm = Lfm2MoeLM(**m, eos_id=config.get("eos_id"), seed=0)
    lm._params = program_params(*reference.make_params(m, seed))
    jax.block_until_ready(lm._params)
    return lm
