#!/usr/bin/env bash
# Tier-1 verification (ROADMAP.md): the full pytest suite from the repo root,
# plus a quickstart smoke-run and an intra-repo doc-link check.
# Usage: scripts/ci.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# --- doc links: every relative markdown link target must exist -------------
echo "== doc-link check =="
fail=0
for doc in README.md docs/*.md; do
  [ -f "$doc" ] || continue
  base=$(dirname "$doc")
  # extract (path) targets of markdown links; keep repo-relative ones only,
  # stripping any #fragment so anchored links are checked too
  for target in $(grep -o '](\([^)]*\))' "$doc" | sed 's/](//; s/)$//' \
                   | grep -v '^https\?://' | grep -v '^mailto:'); do
    case "$target" in *'"'*) continue ;; esac   # titled-link fragments
    path="${target%%#*}"
    [ -z "$path" ] && continue
    if [ ! -e "$base/$path" ] && [ ! -e "$path" ]; then
      echo "BROKEN LINK: $doc -> $target"
      fail=1
    fi
  done
done
[ "$fail" -eq 0 ] || { echo "doc-link check failed"; exit 1; }
echo "doc links ok"

# --- static analysis: lint vs baseline, Pallas contract check against live
# kernel launches, retrace guard on a warmed engine (repro.analysis) --------
echo "== static analysis =="
python -m repro.analysis

# --- quickstart smoke: the three impls must still agree --------------------
echo "== examples/quickstart.py smoke =="
python examples/quickstart.py

# --- observability: launcher smoke with metrics + profiler spans ----------
# Serve a small batch with the span-enabled Observer under a jax.profiler
# trace, then validate both exports: the Perfetto file must hold one
# engine.step span per engine step (numbered in order, none overlapping)
# with every prefill-chunk/decode/sync/retire span inside one, and the
# Prometheus exposition must pass the format checker
# (docs/observability.md).
echo "== observability =="
OBS_TMP=$(mktemp -d)
python -m repro.launch.serve --arch qwen2-7b --size shrink --requests 4 --slots 2 \
  --max-new 4 --prefix-cache \
  --metrics-out "$OBS_TMP/metrics.prom" --trace-out "$OBS_TMP/trace"
python - "$OBS_TMP" <<'EOF'
import glob, gzip, json, sys
from repro.obs.metrics import validate_prometheus_text
tmp = sys.argv[1]
text = open(f"{tmp}/metrics.prom").read()
n = validate_prometheus_text(text)
assert n > 100, f"suspiciously small exposition ({n} samples)"
(path,) = glob.glob(f"{tmp}/trace/**/perfetto_trace.json.gz", recursive=True)
evs = [e for e in json.load(gzip.open(path))["traceEvents"]
       if e.get("ph") == "X" and e.get("name", "").startswith("engine.")]
steps = sorted((e["ts"], e["ts"] + e["dur"], int(e["args"]["step_num"]))
               for e in evs if e["name"] == "engine.step")
want = [line for line in text.splitlines()
        if line.startswith("repro_engine_steps_total ")]
assert len(steps) == int(float(want[0].split()[1])) > 0, (len(steps), want)
assert [k for _, _, k in steps] == list(range(len(steps))), "step_num order"
assert all(a[1] <= b[0] for a, b in zip(steps, steps[1:])), "steps overlap"
inner = [e for e in evs if e["name"] in ("engine.prefill_chunk",
         "engine.decode", "engine.sync", "engine.retire")]
assert inner and all(any(s <= e["ts"] and e["ts"] + e["dur"] <= t
                         for s, t, _ in steps) for e in inner), \
    "an engine span outside every engine.step"
print(f"observability ok: {len(steps)} engine.step spans, {len(evs)} "
      f"engine spans, {n} exposition samples")
EOF
rm -rf "$OBS_TMP"

# --- multi-device: mesh-sharded serving ------------------------------------
# Fresh processes with 8 forced host devices (the main suite above must
# keep its 1-device view — tests/conftest.py): the TP parity matrix
# (token-identical outputs, per-device KV bytes 1/TP) and the TP=2
# retrace gate (O(1) census).
echo "== multi-device (XLA_FLAGS=--xla_force_host_platform_device_count=8) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m pytest -x -q tests/test_mesh_serving.py
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m repro.analysis --no-lint --no-kernel-check

# --- full test suite -------------------------------------------------------
exec python -m pytest -x -q "$@"
