"""The reference check: does the system compute what the paper says?

Before the window, at the published widths, a second, deterministic
Program is built by the same builder in the same scope (same parameter
names, dropout 0, batch norm on batch statistics, append_backward and no
optimizer, so no weight moves; `clone(for_test=True)` will not do: it
prunes the backward ops and switches batch norm to its running
statistics). The parameters are read out of the scope and given to the
configuration's plain reference, and both run a small seeded sample. The
loss and the gradients of the named parameters must agree within the
tolerance the configuration's file states with its reason.

A configuration names its checks under `checks`: {name: entry}. An entry
holds `sample`, `grads`, `tolerance` and `why`, and may set `amp` (the
check Program's arithmetic, where it is not the configuration's) and
`matmul_precision` (jax's default precision while the check Program is
traced: the chip multiplies float32 operands in bf16 passes unless told
otherwise).
"""
import contextlib
import time

import numpy as np


def rel_norm(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def parameter_names(main):
    """A Program's parameters in creation order (the builders walk them to
    hand the reference its tree)."""
    from paddle_tpu.fluid import framework
    return [v.name for v in main.list_vars()
            if isinstance(v, framework.Parameter)]


def grad_paths(tree, names):
    """{Fluid parameter name: (reference path, index or None)} from a
    builder's {reference path: Fluid name or list of names}."""
    out = {}
    for path, v in tree.items():
        for i, n in enumerate(v if isinstance(v, list) else [v]):
            if n in names:
                out[n] = (path, i if isinstance(v, list) else None)
    return out


def run_checks(cell, exe, scope, seed, mesh=None):
    """{name: run_check's result} for every entry of `checks`."""
    return {name: run_check(cell, exe, scope, seed, entry, mesh=mesh)
            for name, entry in sorted(cell['config']['checks'].items())}


def run_check(cell, exe, scope, seed, check, mesh=None):
    """{'passed', 'loss_rel', 'grad_rel': {name: rel}, 'seconds', ...}"""
    import jax
    # the builder sees the entry it builds for and the arithmetic it asks
    config = dict(cell['config'], check=check,
                  amp=check.get('amp', cell['config']['amp']))
    sample = check['sample']
    if mesh:
        chips = int(np.prod(list(mesh.values())))
        sample = -(-sample // chips) * chips       # a whole row per chip
    clock = [time.perf_counter()]

    def lap():
        clock.append(time.perf_counter())
        return clock[-1] - clock[-2]

    traffic = dict(cell['traffic'], batch=sample, pool=1)
    pool, _ = cell['generator'].make_pool(traffic, config, seed + 1)
    built = cell['builder'].build(config, traffic, train=False)
    if mesh:
        built['main'].set_mesh(dict(mesh))
    names = sorted(built['grads'])
    if names != sorted(check['grads']):
        raise ValueError('check parameters %r not all in the Program (%r)'
                         % (check['grads'], names))
    seconds = {'build': lap()}
    precision = check.get('matmul_precision')
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        out = exe.run(built['main'], feed=pool[0],
                      fetch_list=[built['loss']] + [built['grads'][n]
                                                    for n in names])
    loss = float(np.asarray(out[0]).reshape(-1)[0])
    grads = dict(zip(names, out[1:]))
    seconds['program'] = lap()       # lowering, compile or cache read, run

    def read(name):
        return np.asarray(scope.find_var(name).get_tensor())

    params, tree = cell['builder'].reference_params(config, built['main'],
                                                    read)
    seconds['read_parameters'] = lap()
    paths = grad_paths(tree, set(names))
    ref_loss, ref_grads = cell['reference'].loss_and_grads(
        params, config['model'], pool[0], sorted({p for p, _ in
                                                  paths.values()}))
    ref_loss = float(ref_loss)
    seconds['reference'] = lap()
    grad_rel = {}
    for n in names:
        path, idx = paths[n]
        want = ref_grads[path] if idx is None else ref_grads[path][idx]
        grad_rel[n] = rel_norm(grads[n], np.asarray(want))
    loss_rel = abs(loss - ref_loss) / abs(ref_loss)
    tol = check['tolerance']
    passed = bool(np.isfinite(loss) and loss_rel <= tol['loss']
                  and all(r <= tol['grad'] for r in grad_rel.values()))
    return {'passed': passed, 'loss': loss, 'reference_loss': ref_loss,
            'loss_rel': loss_rel, 'grad_rel': grad_rel, 'sample': sample,
            'tolerance': tol, 'seconds': seconds}
