"""The program's Jamba-shaped class under the benchmark's weights
(``configs/*.json`` with ``"served": "jamba"``).

``build`` constructs ``zoo.jamba.JambaLM`` from the configuration's
``model`` block (the published ``config.json`` keys, and ``dtype``) and
puts the reference module's weights in place. The reference makes them
on the device, a layer a call, already rounded to the configuration's
dtype; the program keeps its matrices in that dtype and what it
computes in float32 (norm weights, the convolution's taps and bias,
``b_dt``, ``D``) as float32 holding the same rounded values, and the
recurrence's ``A`` as ``-exp(A_log)`` in the ``[N, Di]`` layout its
state has. The other names are the same on both sides.
"""
from __future__ import annotations

FLOAT32 = ("input_layernorm", "pre_ff_layernorm", "conv_w", "conv_b",
           "b_dt", "dt_norm", "b_norm", "c_norm", "D")


def program_layer(w: dict) -> dict:
    import jax.numpy as jnp
    out = {k: (v.astype(jnp.float32) if k in FLOAT32 else v)
           for k, v in w.items() if k != "A_log"}
    if "A_log" in w:
        out["A"] = -jnp.exp(w["A_log"].astype(jnp.float32)).T
    return out


def program_params(emb: dict, layers: list) -> dict:
    import jax.numpy as jnp
    return {"embed": emb["embed"],
            "final_layernorm": emb["final_layernorm"].astype(jnp.float32),
            "layers": [program_layer(w) for w in layers]}


def build(config: dict, seed: int, reference):
    """The model ``register_generator`` is handed."""
    import jax
    from deeplearning4j_tpu.zoo.jamba import JambaLM

    m = config["model"]
    lm = JambaLM(**m, eos_id=config.get("eos_id"), seed=0)
    lm._params = program_params(*reference.make_params(m, seed))
    jax.block_until_ready(lm._params)
    return lm
