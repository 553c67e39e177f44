"""The optimizer of the language-model builders, from a configuration's
`optimizer` entry: ONE function, so that a schedule is written once.

`schedule: linear_warmup` makes `learning_rate` the PEAK of a linear
warm-up over `warmup_steps` (the window is the warm-up's first steps:
at the peak from step 0 the routers of a held share collapse within
four steps and the held rows are the seed's draw, each file's
`assumed.optimizer`). A configuration that names no schedule gets the
plain constant, with no op added to its Program.

No builder of a configuration: harness/catalog.py loads builders by the
name a configuration gives, and none names this file.
"""
import paddle_tpu.fluid as fluid


def learning_rate(opt):
    """The rate Adam takes: a float, or the schedule's variable (built
    into the default main Program, so call it under the program guard)."""
    schedule = opt.get('schedule')
    if schedule is None:
        return opt['learning_rate']
    if schedule != 'linear_warmup':
        raise ValueError('unknown optimizer.schedule %r' % (schedule,))
    # noam_decay(d, w) climbs linearly to (d w)^-0.5 at step w and falls
    # as step^-0.5 after it: `learning_rate` is the peak
    peak, warmup = opt['learning_rate'], opt['warmup_steps']
    return fluid.layers.learning_rate_scheduler.noam_decay(
        1.0 / (peak * peak * warmup), warmup)


def adam(opt):
    return fluid.optimizer.Adam(
        learning_rate=learning_rate(opt), beta1=opt['beta1'],
        beta2=opt['beta2'], epsilon=opt['epsilon'])
