"""For one test (``test_rehearsal_reference.py``): the timed path broken
underneath the harness. On the children's ``PYTHONPATH`` this module is
imported by every child as it starts and acts in the engine child alone
(``launch_engine.py``): every logit the served model produces moves to the
next id, so each token and log-probability is altered where it is produced.
The harness knows nothing of it and has to report ``correct: false``."""

import sys

if sys.argv and sys.argv[0].endswith("launch_engine.py"):
    import jax.numpy as jnp

    from production_stack_tpu.models import llama as prog

    _forward = prog.Llama.forward

    def _broken(self, *args, **kwargs):
        logits, kv_cache = _forward(self, *args, **kwargs)
        return jnp.roll(logits, 1, axis=-1), kv_cache

    prog.Llama.forward = _broken
