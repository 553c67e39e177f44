#!/usr/bin/env bash
# tools/lint.sh — the repo's static-check step (README "Lint"):
#   1. python -m compileall over the tree (syntax);
#   2. pyflakes over paddle_tpu/ + tools/ when the container has it
#      (undefined names / redefinitions are fatal; unused-import noise is
#      filtered — the tree uses bare "# noqa" markers pyflakes ignores);
#   3. exports the mnist inference artifact and runs tools/program_lint.py
#      over it — the program verifier linting a real saved __model__.
#      Both artifact lints run
#      with --cost --hbm-budget, so a per-device residency regression
#      past the budget fails the script (HbmOverBudget exits 1).
#
# One-liner: bash tools/lint.sh          (LINT_DIR=... to keep the artifact)
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== lint: compileall =="
python -m compileall -q paddle_tpu tools tests chipbench chip_smoke.py

echo "== lint: pyflakes =="
if python -c 'import pyflakes' 2>/dev/null; then
    # keep only the hard errors: undefined names, duplicate defs, syntax
    out=$(python -m pyflakes paddle_tpu tools 2>&1 \
          | grep -E "undefined name|redefinition|duplicate argument|syntax" \
          || true)
    if [ -n "$out" ]; then
        echo "$out"
        echo "pyflakes: hard errors above"
        exit 1
    fi
    echo "pyflakes: clean"
else
    echo "pyflakes not installed in this container; skipped"
fi

echo "== lint: program_lint on exported mnist artifact =="
if [ -z "${LINT_DIR:-}" ]; then
    LINT_DIR=$(mktemp -d /tmp/paddle_tpu_lint.XXXXXX)
    trap 'rm -rf "$LINT_DIR"' EXIT    # default dir is disposable
fi
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
python - "$LINT_DIR" <<'PY'
import sys

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name

out = sys.argv[1]
main, startup = framework.Program(), framework.Program()
with unique_name.guard(), framework.program_guard(main, startup):
    from paddle_tpu.models import mnist
    # build the book graph only; no reader data is touched for an export
    img = fluid.layers.data(name='img', shape=[1, 28, 28], dtype='float32')
    prediction = mnist.cnn_model(img)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.executor.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(out, ['img'], [prediction], exe, main)
print('exported mnist artifact to %s' % out)
PY
# --cost --hbm-budget: the static cost model prices the artifact and
# FAILS the script (HbmOverBudget is error-severity -> exit 1) if the
# mnist model's per-device residency ever regresses past 16 MiB — a
# budget ~3x today's footprint, so growth is intentional, not silent
python tools/program_lint.py "$LINT_DIR" --concurrent --cost \
    --hbm-budget 16M

echo "== lint: program_lint on exported step-form decode artifact =="
python - "$LINT_DIR/decode_step" <<'PY'
import sys

import numpy as np

from paddle_tpu import serving

out = sys.argv[1]
rng = np.random.RandomState(0)
V, E, D, H = 20, 8, 6, 8
weights = {
    'w_dec': (rng.randn(E + D, 4 * H) * 0.3).astype(np.float32),
    'u_dec': (rng.randn(H, 4 * H) * 0.3).astype(np.float32),
    'b_dec': (rng.randn(1, 4 * H) * 0.1).astype(np.float32),
    'w_q': (rng.randn(H, D) * 0.3).astype(np.float32),
    'w_emb': (rng.randn(V, E) * 0.3).astype(np.float32),
    'w_out': (rng.randn(H, V) * 0.3).astype(np.float32),
    'b_out': (rng.randn(1, V) * 0.1).astype(np.float32),
}
eng = serving.DecodeEngine(weights, serving.DecodeConfig(
    slots=2, beam_size=3, max_len=8, src_cap=5))
try:
    eng.export_step_program(out)
finally:
    eng.shutdown()
print('exported step-form decode artifact to %s' % out)
PY
python tools/program_lint.py "$LINT_DIR/decode_step" --cost \
    --hbm-budget 4M
echo "lint: OK"
