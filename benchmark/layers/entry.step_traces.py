"""How often the trainer traced a step program in this process: the
registry's ``train.step_traces`` at the run's end (the step bodies count
themselves at trace time). Every trace is tracing, lowering and a compile or
cache load in set-up; a second one is the step-2 retrace."""


def read(facts):
    from pipe_tpu.obs.telemetry import get_registry
    return get_registry().snapshot().get("train.step_traces")
