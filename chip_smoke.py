"""Bring-up smoke test: the serving main path on a TPU, end to end.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # four chips: TP=4 against one chip

Everything runs in this one process (a chip belongs to one process).  It
refuses to run, and prints no result, unless JAX's first device is a TPU.

(a) Every main-path Pallas kernel (``repro.kernels.catalog``) at qwen2-7b's
    published widths is compiled — ``tpu_custom_call`` in its executable,
    never the interpreter — and matches its XLA oracle on seeded operands.
(b) qwen2-7b at published widths (all 28 layers, bf16 params and KV,
    random weights from ``--seed``) serves 8 seeded requests through the
    paged, chunked-prefill engine twice, with ``impl="pallas"`` and with
    ``impl="xla"`` on the same params.  Both serve 8/8; their first-token
    logits (the last prompt position) agree within ``LOGITS_RTOL``; the
    executable census is <= 3 and a warm batch compiles nothing.
(c) The same model with int8 KV, the prefix cache and speculative decoding
    serves shared-system-prompt requests: every request served, prefix
    hits > 0, drafts verified > 0, and the same greedy tokens as the same
    requests served without speculation.

Every Pallas engine's steps that ran (chunked prefill, decode or verify)
are compiled again at the arguments they ran with, and each executable
must hold ``tpu_custom_call``: the engine ran the compiled kernels.

``--four-chips`` runs only the phase (b) Pallas engine tensor-parallel over
a ("data", "model") = (1, 4) mesh, after the same requests unsharded on one
chip, and checks logits within the same tolerance, 1/4 of the KV bytes on
every device and params spread over all four.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase raises, so the process exits non-zero without it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ARCH = "qwen2-7b"
# the engine's slots, sequence length, page and chunk are the sizes the
# kernel catalog checks (repro.kernels.catalog, imported once src/ is on
# the path)
N_REQUESTS, NEW_TOKENS = 8, 32
PROMPT_LENS = (64, 1500)
# bf16 weights and KV through 28 layers: the two impls round differently
# in every layer, so first-token logits are held to 5% of the largest
# reference logit (max-abs error).  Kernel phase: 2% (one launch).
LOGITS_RTOL = 5e-2
KERNEL_RTOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-6))


def peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 2 ** 30:.2f} GiB"


# ---------------------------------------------------------------------------
# phase (a): kernels
# ---------------------------------------------------------------------------

def phase_kernels(seed: int) -> None:
    import jax
    from repro.configs.base import get_config
    from repro.kernels.catalog import main_path_cases
    from repro.obs.trace import now

    for i, case in enumerate(main_path_cases(get_config(ARCH))):
        ops = jax.jit(case.make)(jax.random.PRNGKey(seed + i))
        t0 = now()
        compiled = jax.jit(case.kernel).lower(*ops).compile()
        t_compile = now() - t0
        assert "tpu_custom_call" in compiled.as_text(), \
            f"{case.name}: no compiled Pallas kernel in the executable"
        outs = jax.tree_util.tree_leaves(compiled(*ops))
        with jax.default_matmul_precision("highest"):
            refs = jax.tree_util.tree_leaves(jax.jit(case.reference)(*ops))
        errs = [rel_err(o, r) for o, r in zip(outs, refs)]
        log(f"(a) {case.name}: compiled in {t_compile:.1f}s, "
            f"max rel err {max(errs):.2e} (limit {KERNEL_RTOL})")
        assert max(errs) <= KERNEL_RTOL, (case.name, errs)
    log("(a) PASS: every main-path kernel compiled and matched its oracle")


# ---------------------------------------------------------------------------
# serving helpers
# ---------------------------------------------------------------------------

def requests(seed: int, vocab: int, shared: list | None = None):
    import numpy as np
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(N_REQUESTS):
        if shared is None:
            n = int(rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1))
            prompt = rng.integers(0, vocab, n).tolist()
        else:
            tail = rng.integers(0, vocab, int(rng.integers(16, 256)))
            prompt = shared + tail.tolist()
        reqs.append(Request(rid=i, tokens=prompt, max_new=NEW_TOKENS))
    return reqs


class Tap:
    """Stands in for one of an engine's jitted steps.  Lowers the step at
    the live arguments of its first call, so the step that ran can be
    compiled again and its HLO read; on the decode step it also keeps, per
    request, the logits of its first decode: the next-token distribution at
    the last prompt position, right after chunked prefill."""

    STEPS = ("_prefill_chunk_exec", "_decode", "_verify")

    def __init__(self, engine, name: str):
        self.engine, self.name, self.fn = engine, name, getattr(engine, name)
        self.lowered, self.rows = None, {}
        setattr(engine, name, self)

    def _cache_size(self):            # the engine's census reads this
        return self.fn._cache_size()

    def __call__(self, *args, **kw):
        import numpy as np
        if self.lowered is None:      # before the call donates the caches
            self.lowered = self.fn.lower(*args, **kw)
        out = self.fn(*args, **kw)
        if self.name == "_decode":
            act = np.asarray(kw["active"])
            for i, req in enumerate(self.engine.slot_req):
                if req is not None and act[i] and not req.out:
                    self.rows[req.rid] = np.asarray(out[0][i], np.float32)
        return out


def tap(engine) -> dict:
    return {name: Tap(engine, name) for name in Tap.STEPS}


def kernels_compiled(taps: dict, label: str) -> None:
    """Each step the engine ran, compiled at the arguments it ran with,
    must launch compiled Pallas kernels, never interpreted ones."""
    ran = []
    for t in taps.values():
        if t.lowered is None:
            continue
        text = t.lowered.compile().as_text()
        n = text.count("tpu_custom_call")
        assert n > 0, f"{label}: {t.name} launched no compiled Pallas kernel"
        ran.append(f"{t.name.strip('_')} {n}")
    assert ran, f"{label}: no step ran"
    log(f"{label}: tpu_custom_call in every step that ran "
        f"({', '.join(ran)} occurrences)")


def serve(engine, reqs, label: str):
    """Serve ``reqs`` to completion; every request must finish cleanly."""
    from repro.obs.trace import now
    t0 = now()
    done = sorted(engine.run(reqs), key=lambda r: r.rid)
    dt = now() - t0
    errors = [(r.rid, r.error) for r in done if r.error is not None]
    assert not errors, f"{label}: failed requests {errors}"
    assert len(done) == len(reqs) and all(
        len(r.out) == r.max_new for r in done), f"{label}: short outputs"
    tokens = sum(len(r.out) for r in done)
    log(f"{label}: served {len(done)}/{len(reqs)} requests, {tokens} new "
        f"tokens ({sum(len(r.tokens) for r in done)} prompt tokens), "
        f"{dt:.1f}s wall incl. compiles")
    return done


def census_ok(engine, label: str) -> None:
    census = engine.compilations
    assert sum(census.values()) <= 3, (label, census)
    log(f"{label}: executable census {census}")


def warm_batch(engine, seed: int, label: str) -> None:
    """A fresh batch on the warmed engine must compile nothing."""
    from repro.analysis.retrace_guard import retrace_guard
    reqs = requests(seed + 1000, engine.cfg.vocab_size)[:engine.n_slots]
    for r in reqs:
        r.rid, r.tokens = r.rid + 1000, r.tokens[:200]
    with retrace_guard(engine, label=label):
        serve(engine, reqs, f"{label} warm")
    log(f"{label}: warm batch, zero new compilations")


def release(engine) -> None:
    """Drop an engine's caches before the next one is built."""
    engine.caches = None
    gc.collect()


def compare_logits(a: dict, b: dict, label: str) -> None:
    import numpy as np
    errs = {rid: rel_err(a[rid], b[rid]) for rid in sorted(b)}
    agree = sum(int(np.argmax(a[r]) == np.argmax(b[r])) for r in b)
    log(f"{label}: first-token logits max rel err "
        f"{max(errs.values()):.2e} (limit {LOGITS_RTOL}), greedy first "
        f"tokens agree {agree}/{len(b)}")
    assert len(errs) == N_REQUESTS and max(errs.values()) <= LOGITS_RTOL, \
        (label, errs)


def token_agreement(a, b) -> str:
    same = sum(x == y for ra, rb in zip(a, b) for x, y in zip(ra.out, rb.out))
    return f"{same}/{sum(len(r.out) for r in b)}"


def shared_prompt_requests(seed: int, vocab: int):
    import numpy as np
    rng = np.random.default_rng(seed + 7)
    phrase = rng.integers(0, vocab, 64).tolist()
    shared = phrase * 8                     # a 512-token system prompt
    return requests(seed + 7, vocab, shared)


# ---------------------------------------------------------------------------
# phases (b) and (c): the engine
# ---------------------------------------------------------------------------

def engine_kw(**over):
    from repro.kernels import catalog
    kw = dict(n_slots=catalog.SLOTS, max_seq=catalog.MAX_SEQ,
              page_size=catalog.PAGE, chunk=catalog.CHUNK)
    kw.update(over)
    return kw


def phase_engine(seed: int):
    import jax
    from repro.launch.serve import build_engine

    dev = jax.devices()[0]
    eng = build_engine(ARCH, size="published", seed=seed, impl="pallas",
                       **engine_kw())
    cfg = eng.cfg
    log(f"(b) {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads}H/{cfg.num_kv_heads}KV x {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; params "
        f"{sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.params)) / 2 ** 30:.2f} GiB "
        f"{jax.numpy.dtype(eng.dtype).name}, "
        f"KV pool {eng.cache_bytes_per_device() / 2 ** 20:.0f} MiB")
    taps = tap(eng)
    pallas = serve(eng, requests(seed, cfg.vocab_size), "(b) pallas")
    pallas_logits = dict(taps["_decode"].rows)
    census_ok(eng, "(b) pallas")
    kernels_compiled(taps, "(b) pallas")
    warm_batch(eng, seed, "(b) pallas")
    log(f"(b) pallas: peak device memory {peak_gib(dev)}")
    params = eng.params
    release(eng)
    del eng, taps

    eng = build_engine(ARCH, size="published", params=params, impl="xla",
                       **engine_kw())
    taps = tap(eng)
    xla = serve(eng, requests(seed, cfg.vocab_size), "(b) xla")
    census_ok(eng, "(b) xla")
    compare_logits(pallas_logits, taps["_decode"].rows, "(b) pallas vs xla")
    log(f"(b) pallas vs xla: greedy tokens agree "
        f"{token_agreement(pallas, xla)}")
    release(eng)
    del eng, taps
    log("(b) PASS: published-width qwen2-7b served 8/8 through the Pallas "
        "path, logits within tolerance of XLA, census <= 3, no warm retrace")
    return params


def phase_features(seed: int, params) -> None:
    from repro.launch.serve import build_engine

    eng = build_engine(ARCH, size="published", params=params, impl="pallas",
                       **engine_kw(kv_dtype="int8", prefix_cache=True,
                                   speculative=True))
    vocab = eng.cfg.vocab_size
    taps = tap(eng)
    spec = serve(eng, shared_prompt_requests(seed, vocab),
                 "(c) int8+prefix+spec")
    census_ok(eng, "(c) int8+prefix+spec")
    kernels_compiled(taps, "(c) int8+prefix+spec")
    log(f"(c) prefix cache: {eng.prefix_hit_pages} pages / "
        f"{eng.prefix_hit_tokens} tokens reused over "
        f"{eng.prefix_lookups} lookups; speculation: {eng.spec_drafted} "
        f"drafted, {eng.spec_accepted} accepted over {eng.spec_steps} "
        f"verify steps")
    assert eng.prefix_hit_pages > 0, "no prefix-cache hits"
    assert eng.spec_drafted > 0, "no drafts verified"
    release(eng)
    del eng, taps

    # greedy speculation must not change a token: the same requests once
    # more on the same params and int8 KV, decoded one token per step
    eng = build_engine(ARCH, size="published", params=params, impl="pallas",
                       **engine_kw(kv_dtype="int8", prefix_cache=True))
    taps = tap(eng)
    plain = serve(eng, shared_prompt_requests(seed, vocab),
                  "(c) int8+prefix, no speculation")
    kernels_compiled(taps, "(c) int8+prefix, no speculation")
    log(f"(c) speculation vs none: greedy tokens agree "
        f"{token_agreement(spec, plain)}")
    assert [r.out for r in spec] == [r.out for r in plain], \
        "speculative decoding changed greedy tokens"
    release(eng)
    log("(c) PASS: int8 KV + prefix cache + speculation served every "
        "request with prefix hits and the tokens of plain decoding")


def phase_four_chips(seed: int) -> None:
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import build_engine

    assert len(jax.devices()) >= 4, "--four-chips needs four devices"
    eng = build_engine(ARCH, size="published", seed=seed, impl="pallas",
                       **engine_kw())
    taps = tap(eng)
    base = serve(eng, requests(seed, eng.cfg.vocab_size), "(4) one chip")
    base_logits = taps["_decode"].rows
    total_kv = eng.cache_bytes_per_device()
    total_params = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.params))
    release(eng)
    eng.params = None
    del eng, taps
    gc.collect()

    mesh = make_serving_mesh(tp=4)
    eng = build_engine(ARCH, size="published", seed=seed, impl="pallas",
                       mesh=mesh, **engine_kw())
    taps = tap(eng)
    tp = serve(eng, requests(seed, eng.cfg.vocab_size), "(4) TP=4")
    census_ok(eng, "(4) TP=4")
    kernels_compiled(taps, "(4) TP=4")
    compare_logits(taps["_decode"].rows, base_logits, "(4) TP=4 vs one chip")
    log(f"(4) TP=4 vs one chip: greedy tokens agree "
        f"{token_agreement(tp, base)}")

    def per_device(tree):
        held = {d: 0 for d in mesh.devices.flat}
        for leaf in jax.tree_util.tree_leaves(tree):
            for shard in leaf.addressable_shards:
                held[shard.device] += shard.data.nbytes
        return held

    kv = per_device(eng.caches)
    log(f"(4) KV bytes per device {sorted(kv.values())} of {total_kv} "
        f"unsharded; cache_bytes_per_device {eng.cache_bytes_per_device()}")
    assert all(4 * b == total_kv for b in kv.values()), kv
    assert 4 * eng.cache_bytes_per_device() == total_kv
    held = per_device(eng.params)
    log(f"(4) param GiB per device "
        f"{[round(b / 2 ** 30, 2) for b in held.values()]} of "
        f"{total_params / 2 ** 30:.2f} GiB unsharded")
    assert all(0 < b < 0.5 * total_params for b in held.values()), held
    for d in mesh.devices.flat:
        log(f"(4) {d}: peak device memory {peak_gib(d)}")
    log("(4) PASS: TP=4 matched one chip, KV and params spread over four")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the TP=4 engine against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import use_compile_cache
    log(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {use_compile_cache()}")
    log(f"model: {ARCH} at published widths, all layers (no depth cut)")
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        phase_kernels(args.seed)
        params = phase_engine(args.seed)
        phase_features(args.seed, params)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
