"""Serving-level microbench: monolithic vs chunked prefill under a mixed
long/short workload, contiguous vs paged KV cache, and cold-vs-warm
prefix caching under a repeated-prefix workload.

Beyond raw tokens/s, each row reports request-level latency percentiles —
the numbers the Scheduler/Runtime split actually moves:

  * **TTFT** (time to first token, p50/p95): monolithic prefill stalls
    every decode slot while a long prompt prefills head-of-line; chunked
    prefill bounds the stall to one budget-sized chunk per step; a warm
    prefix cache skips the shared head's chunks entirely.
  * **TPOT** (time per output token after the first, p50/p95): how steady
    decode remains while prompts are being prefilled in between.

The ``prefix_cold`` / ``prefix_warm`` rows serve the same shared-system-
prompt workload twice through one prefix-cached engine; the warm row also
reports ``pages_saved`` (pages aliased instead of allocated+prefilled) and
asserts warm outputs are token-identical to cold with executables still
O(1) — the acceptance gate for the prefix cache.

Set ``SERVING_BENCH_TINY=1`` for the CI smoke configuration (small model,
few requests) — scripts/ci.sh runs it so scheduler and prefix-cache
regressions fail CI.
"""
from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.analysis import retrace_guard
from repro.configs.base import get_config, shrink
from repro.core.famous import FamousConfig
from repro.launch.serve import profiler_span
from repro.models import module, transformer
from repro.obs.metrics import Histogram, validate_prometheus_text
from repro.obs.runtime import Observer
from repro.obs.trace import now
from repro.serve.engine import Request, ServingEngine
from repro.serve.paged import PagedCacheConfig

TINY = bool(int(os.environ.get("SERVING_BENCH_TINY", "0")))
# run ONLY the mesh (tp1/tp2/tp4) leg — the ci.sh multi-device stage sets
# this so the sharded rows run in their own forced-8-device process while
# the main TINY bench keeps its 1-device view (see tests/conftest.py)
MESH_ONLY = bool(int(os.environ.get("SERVING_BENCH_MESH_ONLY", "0")))
N_SLOTS = 4
MAX_SEQ = 64 if TINY else 256
PAGE = 16
CHUNK = 16 if TINY else 32
MAX_NEW = 4 if TINY else 16
N_REQ = 6 if TINY else 16
# the speculation rows decode longer: acceptance comes from the drafter
# mining the *generated* history's cycles, which max_new=4 never builds
SPEC_NEW = 16 if TINY else 48
# the kv_int8 rows decode longer still: the quantized pool's memory win
# only shows when live KV (not prefill throughput) is the bottleneck
KV_NEW = 40 if TINY else 160
# documented accuracy gate for kv_int8: max |Δlogit| between the fp and
# int8 decode paths on the trained bench model (measured ~0.27; the bound
# leaves ~2x headroom).  Greedy parity additionally requires the model's
# top-2 logit margins to exceed this drift — true for the trained chain
# model below, NOT for random-init weights, whose near-tie margins flip
# under any lossy cache (see docs/serving.md "Quantized KV").
KV_INT8_LOGIT_BOUND = 0.5


def _requests(cfg, n=N_REQ, seed=0):
    rng = np.random.default_rng(seed)
    # bimodal mix: mostly short prompts plus a few long-context stragglers
    long_lo, long_hi = (MAX_SEQ // 2, MAX_SEQ - MAX_NEW - 1)
    lens = [int(rng.integers(4, 24)) if i % 4
            else int(rng.integers(long_lo, long_hi)) for i in range(n)]
    return [Request(rid=i,
                    tokens=list(rng.integers(0, cfg.vocab_size, size=n_)),
                    max_new=MAX_NEW)
            for i, n_ in enumerate(lens)]


def _prefix_requests(cfg, n=N_REQ, seed=7, rid0=0):
    """The prefix-cache workload: every prompt = one shared 'system prompt'
    (3/4 of usable context) + a short distinct tail."""
    rng = np.random.default_rng(seed)
    head = (MAX_SEQ - MAX_NEW - 8) * 3 // 4
    shared = list(rng.integers(0, cfg.vocab_size, size=head))
    return [Request(rid=rid0 + i,
                    tokens=shared + list(rng.integers(0, cfg.vocab_size,
                                                      size=rng.integers(2, 8))),
                    max_new=MAX_NEW)
            for i in range(n)]


def _cache_bytes(engine) -> int:
    return sum(b.size * b.dtype.itemsize
               for b in jax.tree_util.tree_leaves(engine.caches))


def _pct(xs, q):
    """Latency percentile through the SHARED histogram quantile path
    (repro.obs.metrics) — the bench reports the same numbers a live
    Prometheus ``histogram_quantile`` over the Observer's TTFT/TPOT
    histograms would, bucket quantization included (~12% resolution at
    the default 20-buckets-per-decade schema)."""
    return Histogram.of(xs).percentile(q) if xs else float("nan")


def _timed_run(engine, reqs, label):
    t0 = now()
    done = engine.run(reqs)
    dt = now() - t0
    served = [r for r in done if r.error is None and r.t_first is not None]
    tok = sum(len(r.out) for r in served)
    ttft = [(r.t_first - r.t_submit) * 1e3 for r in served]
    tpot = [(r.t_done - r.t_first) / max(len(r.out) - 1, 1) * 1e3
            for r in served]
    us_per_tok = dt / max(tok, 1) * 1e6
    common.emit(
        f"serving/{label}", us_per_tok,
        f"tok_s={tok/dt:.1f};requests={len(done)};"
        f"ttft_p50_ms={_pct(ttft, 50):.1f};ttft_p95_ms={_pct(ttft, 95):.1f};"
        f"tpot_p50_ms={_pct(tpot, 50):.1f};tpot_p95_ms={_pct(tpot, 95):.1f};"
        f"prefill_execs={engine.prefill_compilations};"
        f"cache_mib={_cache_bytes(engine)/2**20:.2f}")
    return done, ttft


def _bench(params, cfg, label, **kw):
    engine = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                           n_slots=N_SLOTS, max_seq=MAX_SEQ, **kw)
    # warm THIS engine's executables (jit caches are per-instance) with the
    # same length mix as the timed run, so the timed region measures
    # scheduling, not XLA compiles — monolithic mode compiles its whole
    # bucket family here, chunked its two executables (the executable
    # counts in the emitted row keep that asymmetry visible)
    engine.run(_requests(cfg))
    _timed_run(engine, _requests(cfg), label)
    return engine


def _bench_prefix(params, cfg):
    """Cold vs warm rows through ONE prefix-cached engine: run 1 publishes
    the shared head's blocks, run 2 aliases them.  Asserts the acceptance
    gate: warm token-identical to cold, pages saved, executables O(1)."""
    engine = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                           n_slots=N_SLOTS, max_seq=MAX_SEQ,
                           prefill_mode="chunked", chunk=CHUNK,
                           cache_kind="paged", page_size=PAGE,
                           prefix_cache=True)
    # executable warmup with an unrelated prompt mix (different seed, so no
    # hash collisions with the timed workload: the cold row stays cold)
    engine.run(_requests(cfg, seed=99))
    hit0 = engine.prefix_hit_pages
    # both timed rows run on the warm engine: zero new executables allowed
    with retrace_guard(engine, label="prefix cold+warm timed runs"):
        cold, cold_ttft = _timed_run(engine, _prefix_requests(cfg),
                                     "prefix_cold")
        hit1 = engine.prefix_hit_pages  # late cold admissions may already hit
        warm, warm_ttft = _timed_run(engine,
                                     _prefix_requests(cfg, rid0=N_REQ),
                                     "prefix_warm")
    saved = engine.prefix_hit_pages - hit1
    common.emit("serving/prefix_warm_vs_cold",
                _pct(warm_ttft, 50) * 1e3,  # us, for the us-valued column
                f"ttft_p50_cold_ms={_pct(cold_ttft, 50):.1f};"
                f"ttft_p50_warm_ms={_pct(warm_ttft, 50):.1f};"
                f"pages_saved_cold={hit1 - hit0};pages_saved_warm={saved};"
                f"cached_free_pages={engine.alloc.cached_free_pages}")
    outs = [r.out for r in sorted(cold, key=lambda r: r.rid)]
    wout = [r.out for r in sorted(warm, key=lambda r: r.rid)]
    assert outs == wout, "warm prefix-cache outputs must be token-identical"
    assert saved > 0, "warm run must alias cached pages"
    assert _pct(warm_ttft, 50) < _pct(cold_ttft, 50), \
        (f"warm TTFT p50 {_pct(warm_ttft, 50):.1f}ms not below cold "
         f"{_pct(cold_ttft, 50):.1f}ms")


def _spec_requests(cfg, kind, n=N_REQ, seed=3, rid0=0):
    """Speculation workloads.  "repetitive": periodic prompts + greedy —
    the prompt-lookup drafter's best case (the continuation keeps citing
    the prompt's own n-grams).  "adversarial": uniform-random prompts +
    temperature-1 sampling — drafts almost never survive, so every step
    pays the verify width for ~1 accepted token (the worst case the
    regression bound guards)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        n_p = int(rng.integers(6, min(16, MAX_SEQ - SPEC_NEW)))
        if kind == "repetitive":
            motif = list(map(int, rng.integers(0, cfg.vocab_size, 4)))
            toks, kw = (motif * MAX_SEQ)[:n_p], {}
        else:
            toks = list(map(int, rng.integers(0, cfg.vocab_size, n_p)))
            kw = dict(temperature=1.0, seed=rid0 + i)
        reqs.append(Request(rid=rid0 + i, tokens=toks, max_new=SPEC_NEW, **kw))
    return reqs


def _bench_spec(params, cfg):
    """spec_off vs spec_on rows, interleaved best-of-N so scheduler noise
    and one-off compiles cancel.  Gates: drafts actually get accepted and
    tok/s wins on the repetitive workload; the adversarial (near-zero
    acceptance) workload stays within a bounded slowdown of plain decode."""
    rounds = 2 if TINY else 3
    engines = {}
    for label, spec in (("spec_off", False), ("spec_on", True)):
        eng = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                            n_slots=N_SLOTS, max_seq=MAX_SEQ, chunk=CHUNK,
                            speculative=spec, draft_k=4)
        eng.run(_spec_requests(cfg, "repetitive", seed=99, rid0=9000))
        engines[label] = eng
    spec_eng = engines["spec_on"]
    tok_s = {}
    for wl in ("repetitive", "adversarial"):
        drafted0, accepted0 = spec_eng.spec_drafted, spec_eng.spec_accepted
        steps0 = spec_eng.spec_steps
        best = {"spec_off": 0.0, "spec_on": 0.0}
        outs = {}
        for rnd in range(rounds):
            for label, eng in engines.items():
                reqs = _spec_requests(cfg, wl, seed=50 + rnd,
                                      rid0=1000 * rnd)
                t0 = now()
                done = eng.run(reqs)
                dt = now() - t0
                assert all(r.error is None for r in done)
                tok = sum(len(r.out) for r in done)
                best[label] = max(best[label], tok / dt)
                outs.setdefault(rnd, {})[label] = \
                    [r.out for r in sorted(done, key=lambda r: r.rid)]
            # interleaved rounds double as a parity check
            assert outs[rnd]["spec_on"] == outs[rnd]["spec_off"], \
                f"speculative {wl} outputs diverged from plain decode"
        drafted = spec_eng.spec_drafted - drafted0
        accepted = spec_eng.spec_accepted - accepted0
        steps = spec_eng.spec_steps - steps0
        acc = accepted / max(drafted, 1)
        per_step = (steps + accepted) / max(steps, 1)
        for label in ("spec_off", "spec_on"):
            meta = f"tok_s={best[label]:.1f};rounds={rounds}"
            if label == "spec_on":
                meta += (f";acceptance={acc:.3f};"
                         f"accepted_per_step={per_step:.2f};"
                         f"drafted={drafted};accepted={accepted}")
            common.emit(f"serving/{label}_{wl}", 1e6 / max(best[label], 1e-9),
                        meta)
        tok_s[wl] = (best["spec_off"], best["spec_on"], acc)
    off, on, acc = tok_s["repetitive"]
    assert acc > 0, "repetitive workload must accept draft tokens"
    assert on > off, \
        f"speculative tok/s {on:.1f} must beat plain {off:.1f} on the " \
        f"repetitive workload"
    off, on, _ = tok_s["adversarial"]
    assert on > 0.4 * off, \
        f"adversarial speculative tok/s {on:.1f} fell below 0.4x plain " \
        f"{off:.1f} — rejected-draft overhead is unbounded"


def _kv_requests(cfg, n, seed):
    """Decode-heavy workload for the kv_int8 rows: short prompts, long
    generations — live KV pages, not prefill, bound throughput."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=list(rng.integers(0, cfg.vocab_size,
                                             size=int(rng.integers(4, 12)))),
                    max_new=KV_NEW)
            for i in range(n)]


def _train_chain(params, cfg, fcfg):
    """Teach the bench model a deterministic 32-token successor cycle
    (SGD, ~2.5s on CPU) and return (trained_params, chain).

    Greedy-parity gates need a model whose top-1 logit margin exceeds the
    int8 drift; random-init logits are near-ties (~0.01) that argmax-flip
    under ANY lossy cache, so parity is checked on this trained model and
    its in-distribution chain prompts instead."""
    rng = np.random.default_rng(5)
    sub = np.sort(rng.choice(cfg.vocab_size, 32, replace=False))
    cyc = rng.permutation(32)
    succ = {int(sub[cyc[i]]): int(sub[cyc[(i + 1) % 32]]) for i in range(32)}
    chain = [int(sub[cyc[0]])]
    while len(chain) < 512 + 33:
        chain.append(succ[chain[-1]])
    chain = np.array(chain, np.int32)

    @jax.jit
    def sgd_step(p, batch):
        def loss_fn(p):
            logits = transformer.forward(p, batch[:, :-1], cfg, fcfg)
            lp = jax.nn.log_softmax(logits, -1)
            return -jnp.take_along_axis(lp, batch[:, 1:, None], -1).mean()

        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 1.0 * b, p, g), loss

    tparams = params
    for _ in range(120):
        offs = rng.integers(0, 512, size=8)
        batch = jnp.asarray(np.stack([chain[o:o + 33] for o in offs]))
        tparams, loss = sgd_step(tparams, batch)
    assert float(loss) < 0.2, f"chain model failed to train: loss {loss}"
    return tparams, chain


def _chain_requests(chain, n, seed, max_new=8):
    """Prompts cut from the trained chain; prompt+max_new stays inside the
    64 positions the model was trained to generalize over."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        length = int(rng.integers(4, 49))
        off = int(rng.integers(0, 400))
        reqs.append(Request(rid=i,
                            tokens=[int(t) for t in chain[off:off + length]],
                            max_new=max_new))
    return reqs


def _kv_probe(tparams, cfg, fcfg, prompt, max_new, kv_dtype,
              max_seq=64, page=16, chunk=16):
    """Greedy-decode a prompt through the raw paged prefill/decode path and
    return (tokens, per-step logits) — the drift probe the bound gates."""
    n_p = max_seq // page
    pt = jnp.arange(1, n_p + 1, dtype=jnp.int32)[None]
    caches = transformer.make_caches(cfg, 1, max_seq, jnp.float32,
                                     cache_kind="paged", page_size=page,
                                     n_pages=n_p + 1, kv_dtype=kv_dtype)
    n_ctx = len(prompt) - 1
    padded = prompt[:-1] + [0] * (-n_ctx % chunk)
    for off in range(0, len(padded), chunk):
        caches = transformer.prefill_chunk(
            tparams, jnp.asarray([padded[off:off + chunk]], jnp.int32),
            caches, 0, off, min(chunk, n_ctx - off), cfg, fcfg,
            page_table=pt)
    tok, cache_len = prompt[-1], jnp.asarray([n_ctx], jnp.int32)
    toks, logs = [], []
    for _ in range(max_new):
        logits, caches = transformer.decode_step(
            tparams, jnp.asarray([tok], jnp.int32), caches, cache_len,
            cfg, fcfg, page_table=pt)
        logs.append(np.asarray(logits[0], np.float32))
        tok = int(jnp.argmax(logits[0]))
        toks.append(tok)
        cache_len = cache_len + 1
    return toks, np.stack(logs)


def _bench_kv_int8(params, cfg):
    """kv_fp vs kv_int8 rows.  Two legs:

    1. Throughput/capacity at an EQUAL byte budget: the fp pool gets
       pages for ~2 concurrent requests, the int8 pool the same bytes
       (~3.2x the pages at dh=16), and both serve the decode-heavy
       workload interleaved best-of-N.  Gates: bytes-per-token <= 0.55x
       fp, served requests >= fp, tok/s >= fp (fp thrashes preempting,
       int8 keeps all slots resident).
    2. Accuracy on the trained chain model: greedy tokens identical
       between fp and int8 engines, and max |Δlogit| on the raw decode
       path under KV_INT8_LOGIT_BOUND."""
    fcfg = FamousConfig(impl="xla")
    dh = cfg.head_dim
    # int8 payload + one fp32 scale per token-per-kv-head, vs fp32 payload
    bytes_ratio = (dh + 4) / (4 * dh)
    pages_per_req = -(-(12 + KV_NEW) // PAGE)
    fp_pages = 2 * pages_per_req + 1  # null page + ~2 resident requests
    q8_pages = 1 + int((fp_pages - 1) / bytes_ratio)  # same HBM bytes
    n_req, rounds = 8, (2 if TINY else 3)
    engines = {}
    for label, kvd, n_pages in (("kv_fp", "fp", fp_pages),
                                ("kv_int8", "int8", q8_pages)):
        eng = ServingEngine(params, cfg, fcfg, n_slots=N_SLOTS,
                            max_seq=MAX_SEQ, prefill_mode="chunked",
                            chunk=CHUNK, cache_kind="paged", page_size=PAGE,
                            n_pages=n_pages, kv_dtype=kvd)
        eng.run(_kv_requests(cfg, n_req, seed=99))  # warm executables
        engines[label] = eng
    best = {"kv_fp": 0.0, "kv_int8": 0.0}
    served = {"kv_fp": 0, "kv_int8": 0}
    preempt = {"kv_fp": 0, "kv_int8": 0}
    with retrace_guard(engines["kv_fp"], label="kv_fp timed runs"), \
         retrace_guard(engines["kv_int8"], label="kv_int8 timed runs"):
        for rnd in range(rounds):
            for label, eng in engines.items():
                reqs = _kv_requests(cfg, n_req, seed=50 + rnd)
                t0 = now()
                done = eng.run(reqs)
                dt = now() - t0
                ok = [r for r in done if r.error is None]
                best[label] = max(best[label],
                                  sum(len(r.out) for r in ok) / dt)
                served[label] += len(ok)
                preempt[label] += sum(
                    eng.sched.fairness(r.rid).get("preemptions", 0)
                    for r in done)
    bpt = {label: _cache_bytes(eng) / (eng.pcfg.n_pages * PAGE)
           for label, eng in engines.items()}
    for label, eng in engines.items():
        common.emit(
            f"serving/{label}", 1e6 / max(best[label], 1e-9),
            f"tok_s={best[label]:.1f};served={served[label]};"
            f"preemptions={preempt[label]};n_pages={eng.pcfg.n_pages};"
            f"bytes_per_tok={bpt[label]:.0f};rounds={rounds};"
            f"cache_mib={_cache_bytes(eng)/2**20:.2f}")
    ratio = bpt["kv_int8"] / bpt["kv_fp"]
    assert ratio <= 0.55, \
        f"int8 bytes-per-token ratio {ratio:.3f} above the 0.55 gate"
    assert served["kv_int8"] >= served["kv_fp"], \
        f"int8 served {served['kv_int8']} < fp {served['kv_fp']} " \
        f"at the same byte budget"
    assert best["kv_int8"] >= best["kv_fp"], \
        f"int8 tok/s {best['kv_int8']:.1f} below fp " \
        f"{best['kv_fp']:.1f} at the same byte budget"

    # --- accuracy leg: greedy parity + bounded logit drift ---
    tparams, chain = _train_chain(params, cfg, fcfg)
    peng = {}
    for kvd in ("fp", "int8"):
        eng = ServingEngine(tparams, cfg, fcfg, n_slots=N_SLOTS, max_seq=64,
                            prefill_mode="chunked", chunk=16,
                            cache_kind="paged", page_size=16, kv_dtype=kvd)
        eng.run(_chain_requests(chain, 4, seed=98))
        peng[kvd] = eng
    outs = {}
    for kvd, eng in peng.items():
        done = eng.run(_chain_requests(chain, 6 if TINY else 12, seed=11))
        assert all(r.error is None for r in done)
        outs[kvd] = [r.out for r in sorted(done, key=lambda r: r.rid)]
    assert outs["fp"] == outs["int8"], \
        "int8 greedy tokens diverged from fp on the trained bench workload"
    drift = 0.0
    for req in _chain_requests(chain, 3, seed=12):
        toks_f, logits_f = _kv_probe(tparams, cfg, fcfg, req.tokens, 8, "fp")
        toks_q, logits_q = _kv_probe(tparams, cfg, fcfg, req.tokens, 8,
                                     "int8")
        assert toks_f == toks_q
        drift = max(drift, float(np.abs(logits_f - logits_q).max()))
    assert drift <= KV_INT8_LOGIT_BOUND, \
        f"int8 logit drift {drift:.3f} above bound {KV_INT8_LOGIT_BOUND}"
    common.emit("serving/kv_int8_parity", drift * 1e6,
                f"max_dlogit={drift:.4f};bound={KV_INT8_LOGIT_BOUND};"
                f"greedy_identical=1;"
                f"parity_requests={len(outs['fp'])}")


def _bench_obs(params, cfg):
    """``obs_off`` vs ``obs_on`` rows: two otherwise-identical paged
    engines, one carrying a full Observer (metrics + the engine's spans
    through ``jax.profiler.TraceAnnotation``), served
    interleaved best-of-N.  Gates the observability overhead contract
    (docs/observability.md): observer-on outputs token-identical to off,
    and tok/s within 5% (measured ≤2%; the CI gate leaves noise
    headroom).  The exposition the Observer produced is also pushed
    through the format checker so a malformed dump fails the bench, not
    just the unit tests."""
    rounds = 4 if TINY else 3
    obs = Observer(span=profiler_span)
    engines = {}
    for label, o in (("obs_off", None), ("obs_on", obs)):
        eng = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                            n_slots=N_SLOTS, max_seq=MAX_SEQ,
                            prefill_mode="chunked", chunk=CHUNK,
                            cache_kind="paged", page_size=PAGE, observer=o)
        eng.run(_requests(cfg, seed=99))            # warm the executables
        engines[label] = eng

    # decode-heavy workload: short prompts, SPEC_NEW-long generations, so
    # each timed run is long enough that host noise doesn't swamp the
    # ~1-2% hook cost the gate is after
    def _obs_requests(seed):
        rng = np.random.default_rng(seed)
        return [Request(rid=i,
                        tokens=list(rng.integers(0, cfg.vocab_size,
                                                 size=int(rng.integers(4, 14)))),
                        max_new=SPEC_NEW)
                for i in range(N_REQ)]

    best = {"obs_off": 0.0, "obs_on": 0.0}
    best_ratio = 0.0
    with retrace_guard(engines["obs_off"], engines["obs_on"],
                       label="obs_off/obs_on timed runs"):
        for rnd in range(rounds):
            outs, rate = {}, {}
            # alternate which engine goes first so slow drift (thermal,
            # co-tenant load) cancels out of the per-round ratio
            order = ("obs_off", "obs_on") if rnd % 2 == 0 \
                else ("obs_on", "obs_off")
            for label in order:
                reqs = _obs_requests(60 + rnd)
                t0 = now()
                done = engines[label].run(reqs)
                dt = now() - t0
                rate[label] = sum(len(r.out) for r in done) / dt
                best[label] = max(best[label], rate[label])
                outs[label] = [r.out for r in sorted(done,
                                                     key=lambda r: r.rid)]
            assert outs["obs_on"] == outs["obs_off"], \
                "observer-on outputs must be token-identical to observer-off"
            best_ratio = max(best_ratio, rate["obs_on"] / rate["obs_off"])
    snap = obs.snapshot()
    n_samples = validate_prometheus_text(obs.prometheus_text())
    for label in ("obs_off", "obs_on"):
        meta = f"tok_s={best[label]:.1f};rounds={rounds}"
        if label == "obs_on":
            meta += (f";best_on_off_ratio={best_ratio:.3f};"
                     f"exposition_samples={n_samples};"
                     f"tokens_counted="
                     f"{snap.get('repro_tokens_generated_total', 0):.0f}")
        common.emit(f"serving/{label}", 1e6 / max(best[label], 1e-9), meta)
    # overhead gate: within any single round (temporally adjacent runs of
    # the same workload) the observed engine must reach 95% of the bare
    # engine's throughput at least once — measured cost is ~1-2%, the
    # headroom is CPU-timer noise (docs/observability.md)
    assert best_ratio >= 0.95, \
        f"observer overhead gate: best obs_on/obs_off ratio " \
        f"{best_ratio:.3f} below 0.95 " \
        f"(best tok/s on={best['obs_on']:.1f} off={best['obs_off']:.1f})"


def _bench_mesh():
    """Interleaved ``tp1``/``tp2``/``tp4`` rows on a paged engine over the
    forced-host-device mesh.  Gates: outputs token-identical across TP,
    per-device KV bytes exactly 1/TP of unsharded (the bench config's 4 kv
    heads divide every TP), census O(1) under retrace_guard.  Skips (with a
    note) when fewer than 4 devices are visible — scripts/ci.sh runs this
    leg under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    if jax.device_count() < 4:
        print("# serving/mesh: SKIPPED — needs >= 4 devices (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8)")
        return
    from repro.launch.mesh import make_serving_mesh
    cfg = shrink(get_config("qwen2-7b"), num_heads=8, num_kv_heads=4,
                 head_dim=8)
    params = module.init_params(transformer.model_spec(cfg),
                                jax.random.PRNGKey(0), jnp.float32)
    tps, rounds = (1, 2, 4), (2 if TINY else 3)
    engines = {}
    for tp in tps:
        eng = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                            n_slots=N_SLOTS, max_seq=MAX_SEQ, chunk=CHUNK,
                            cache_kind="paged", page_size=PAGE,
                            mesh=make_serving_mesh(tp=tp) if tp > 1 else None)
        eng.run(_requests(cfg, seed=99))            # warm the executables
        engines[tp] = eng
    best = {tp: 0.0 for tp in tps}
    ttft = {tp: [] for tp in tps}
    tpot = {tp: [] for tp in tps}
    with contextlib.ExitStack() as stack:
        for tp in tps:
            stack.enter_context(retrace_guard(engines[tp],
                                              label=f"tp{tp} timed runs"))
        for rnd in range(rounds):
            outs = {}
            for tp in tps:
                reqs = _requests(cfg, seed=50 + rnd)
                t0 = now()
                done = engines[tp].run(reqs)
                dt = now() - t0
                ok = [r for r in done
                      if r.error is None and r.t_first is not None]
                best[tp] = max(best[tp], sum(len(r.out) for r in ok) / dt)
                ttft[tp] += [(r.t_first - r.t_submit) * 1e3 for r in ok]
                tpot[tp] += [(r.t_done - r.t_first) / max(len(r.out) - 1, 1)
                             * 1e3 for r in ok]
                outs[tp] = [r.out for r in sorted(done, key=lambda r: r.rid)]
            assert outs[2] == outs[1] and outs[4] == outs[1], \
                "sharded outputs diverged from the unsharded engine"
    kvb = {tp: engines[tp].cache_bytes_per_device() for tp in tps}
    for tp in tps:
        common.emit(
            f"serving/tp{tp}", 1e6 / max(best[tp], 1e-9),
            f"tok_s={best[tp]:.1f};"
            f"ttft_p50_ms={_pct(ttft[tp], 50):.1f};"
            f"ttft_p95_ms={_pct(ttft[tp], 95):.1f};"
            f"tpot_p50_ms={_pct(tpot[tp], 50):.1f};"
            f"tpot_p95_ms={_pct(tpot[tp], 95):.1f};"
            f"kv_bytes_per_device={kvb[tp]};rounds={rounds}")
    assert kvb[2] * 2 == kvb[1] and kvb[4] * 4 == kvb[1], \
        f"per-device KV bytes must shrink 1/TP, got {kvb}"
    for tp in tps:
        c = engines[tp].compilations
        assert c["prefill"] == 1 and c["decode"] == 1, (tp, c)


def run():
    if MESH_ONLY:
        print("# serving-level: mesh-sharded (tp1/tp2/tp4) leg only")
        _bench_mesh()
        return
    print("# serving-level: continuous batching under a mixed long/short "
          "workload (CPU) — monolithic vs chunked prefill, contiguous vs "
          "paged KV cache, cold vs warm prefix cache; TTFT/TPOT in ms")
    cfg = shrink(get_config("qwen2-7b"))
    params = module.init_params(transformer.model_spec(cfg),
                                jax.random.PRNGKey(0), jnp.float32)
    _bench(params, cfg, "monolithic", prefill_mode="monolithic")
    eng = _bench(params, cfg, "chunked", prefill_mode="chunked", chunk=CHUNK)
    assert eng.prefill_compilations == 1, eng.compilations  # CI tripwire
    _bench(params, cfg, "chunked_paged", prefill_mode="chunked", chunk=CHUNK,
           cache_kind="paged", page_size=PAGE)
    _bench_prefix(params, cfg)
    _bench_obs(params, cfg)
    _bench_spec(params, cfg)
    _bench_kv_int8(params, cfg)
    _bench_mesh()   # prints a skip note on a 1-device host
    if not TINY:
        half = max(2, PagedCacheConfig.default_pool(N_SLOTS, MAX_SEQ,
                                                    PAGE) // 2)
        _bench(params, cfg, "chunked_paged_oversubscribed_half_pool",
               prefill_mode="chunked", chunk=CHUNK, cache_kind="paged",
               page_size=PAGE, n_pages=half)


if __name__ == "__main__":
    run()
