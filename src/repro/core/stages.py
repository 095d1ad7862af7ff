"""Names of a round's stages and of the fused driver's host spans, as the
JAX profiler records them.

Device side: every op a round runs carries the name of its stage in its
HLO `op_name` (the name stack a profiler trace files as `tf_op`), because
the function that does the stage's work runs under `jax.named_scope`:

  round.a1_local    Algorithm 1, the workers' local discriminator steps
                    with the fake generator forwards they make (FedGAN:
                    the local discriminator and generator steps)
  round.uplink      Step 3, the uplink quantize/dequantize
  round.a2_average  Algorithm 2: the all-gather, the `wavg`, robust or
                    ring kernel and the no-survivor fallback. The ring
                    impl quantizes inside the collective, so on the ring
                    the uplink falls here.
  round.a3_server   Algorithm 3, the server's generator update

Under transforms the name arrives wrapped, e.g.
`vmap(transpose(jvp(round.a1_local)))/conv_general_dilated`.

Host side: `Trainer._run_fused` records each chunk as a
`trainer.dispatch` step span with the children `trainer.enqueue` (on
the mesh holding `shard_round.signature` and `shard_round.place`),
`trainer.wait`, `trainer.readback` and `trainer.records`. A dispatch
that compiles shows JAX's own compile spans under `trainer.enqueue`.

A named scope is metadata: it changes neither the compiled program nor
its run time. A host span records only while a profiler trace is active
(`jax.profiler.trace`); otherwise it costs one check.
"""
from __future__ import annotations

import functools

import jax

A1_LOCAL = "round.a1_local"
UPLINK = "round.uplink"
A2_AVERAGE = "round.a2_average"
A3_SERVER = "round.a3_server"
STAGES = (A1_LOCAL, UPLINK, A2_AVERAGE, A3_SERVER)

DISPATCH = "trainer.dispatch"
ENQUEUE = "trainer.enqueue"
WAIT = "trainer.wait"
READBACK = "trainer.readback"
RECORDS = "trainer.records"
SIGNATURE = "shard_round.signature"
PLACE = "shard_round.place"


def stage(name: str):
    """Decorator: the function's ops are traced under the stage `name`,
    for every caller."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def span(name: str):
    """A host span on the profiler's clock."""
    return jax.profiler.TraceAnnotation(name)


def dispatch_span(start_round: int):
    """The span of one fused dispatch, numbered by its first round."""
    return jax.profiler.StepTraceAnnotation(DISPATCH, step_num=start_round)
