"""The program's SmallThinker-shaped class under the benchmark's weights
(``configs/*.json`` with ``"served": "smallthinker"``).

``build`` constructs ``zoo.smallthinker.SmallThinkerLM`` from the
configuration's ``model`` block (the published ``config.json`` keys as
this chip holds them, and ``dtype``) and puts the reference module's
weights in place. The reference makes them on the device, a layer a
call, already rounded to the configuration's dtype; the program keeps
its matrices in that dtype and what it computes in float32 (norm
weights, the router) as float32 holding the same rounded values. The
names are the same on both sides.
"""
from __future__ import annotations

FLOAT32 = ("input_layernorm", "post_attention_layernorm", "W_r")


def program_params(emb: dict, layers: list) -> dict:
    import jax.numpy as jnp
    return {"embed": emb["embed"], "head": emb["head"],
            "norm": emb["norm"].astype(jnp.float32),
            "layers": [{k: (v.astype(jnp.float32) if k in FLOAT32 else v)
                        for k, v in w.items()} for w in layers]}


def build(config: dict, seed: int, reference):
    """The model ``register_generator`` is handed."""
    import jax
    from deeplearning4j_tpu.zoo.smallthinker import SmallThinkerLM

    m = config["model"]
    lm = SmallThinkerLM(**m, eos_id=config.get("eos_id"), seed=0)
    lm._params = program_params(*reference.make_params(m, seed))
    jax.block_until_ready(lm._params)
    return lm
