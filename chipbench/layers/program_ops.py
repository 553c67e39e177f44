"""Ops in the global block of the Program the Executor lowers. Where its
pass pipeline ran over the training Program's first step, that is the
clone the passes leave (`ops_after` of the program's `passes.optimize`
span), so a pass that removes or merges an op moves the count; where it
did not run (the program's default today), it is the Program as handed
over. Repeats exactly."""


def read(reading):
    span = reading['pass_span']
    return span['ops_after'] if span else reading['program_ops_handed']
