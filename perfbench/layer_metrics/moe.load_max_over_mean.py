"""Experts: the program's own moe.load_max_over_mean (telemetry.snapshot();
gluon/model_zoo/nemotron_h.py says what it counts), accumulated on the
device by the step programs and read here, once, after the window.  A
program without the counter gives nothing to read."""


def read(obs):
    import mxnet_tpu as mx

    return mx.telemetry.snapshot().get("moe.load_max_over_mean")
