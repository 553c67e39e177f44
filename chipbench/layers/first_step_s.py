"""The benchmark's clock around the first `exe.run` of the training
Program: Python tracing and lowering, then XLA compilation or the read
from the persistent cache, then one step."""


def read(reading):
    return reading['marks'].get('first_step_s')
