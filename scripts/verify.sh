#!/usr/bin/env bash
# Single CI entry point: compat smoke-import check + the tier-1 suite.
# Speed is measured on the chip by bench/ (BENCHMARK.json), not here.
#
#   ./scripts/verify.sh            # full tier-1
#   ./scripts/verify.sh --smoke    # import check only (seconds)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== compat smoke: import every repro module under the installed JAX =="
python - <<'PY'
import importlib, pathlib, sys
src = pathlib.Path("src")
mods = sorted(".".join(p.relative_to(src).with_suffix("").parts)
              for p in src.rglob("*.py") if p.name != "__init__.py")
failed = []
for m in mods:
    try:
        importlib.import_module(m)
    except Exception as e:  # noqa: BLE001 - report everything
        failed.append((m, f"{type(e).__name__}: {e}"))
for m, err in failed:
    print(f"FAIL {m}: {err}")
print(f"{len(mods) - len(failed)}/{len(mods)} modules import cleanly")
sys.exit(1 if failed else 0)
PY

if [[ "${1:-}" == "--smoke" ]]; then
    exit 0
fi

if [[ "${REPRO_FULL_MATRIX:-0}" == "1" ]]; then
    echo "== full scenario matrix (nightly tier: substrate x family x =="
    echo "== codec cross-product + launcher SIGTERM sweep, --runslow) =="
    python -m pytest tests/test_scenario_matrix.py -q --runslow
fi

echo "== tier-1 test suite =="
# Wall-clock budget: tier-1 must stay in its current envelope (~15 min on
# the 1-core CI box).  When it drifts, run with --durations=15 to find the
# hot tests; the scenario matrix keeps only a 6-cell representative subset
# in tier-1 — everything else belongs behind the slow marker.
python -m pytest -x -q
