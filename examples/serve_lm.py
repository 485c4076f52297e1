"""Serving driver: batched requests through the Scheduler/Runtime engine
(token-budgeted chunked prefill into the paged pool, interleaved with
batched decode) on a reduced qwen2-style model — once plain, and once with
the prefix cache (cold then warm) — checking the generated tokens are
identical every way (docs/serving.md), then the int8 KV cache's memory.

    PYTHONPATH=src python examples/serve_lm.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, shrink
from repro.core.famous import FamousConfig
from repro.models import module, transformer
from repro.obs.trace import now
from repro.serve.engine import Request, ServingEngine


def serve(params, cfg, reqs, label, engine=None, **kw):
    engine = engine or ServingEngine(params, cfg, FamousConfig(impl="xla"),
                                     n_slots=4, max_seq=256, **kw)
    t0 = now()
    done = sorted(engine.run(reqs), key=lambda r: r.rid)
    dt = now() - t0
    tok = sum(len(r.out) for r in done)
    print(f"{label:22s}: {len(done)} requests, {tok} new tokens, "
          f"{dt:.2f}s ({tok/dt:.1f} tok/s on 1 CPU core), "
          f"prefill executables: {engine.prefill_compilations}")
    return done, engine


def main():
    cfg = shrink(get_config("qwen2-7b"))
    params = module.init_params(transformer.model_spec(cfg),
                                jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size,
                                 size=int(rng.integers(4, 180))))
               for _ in range(12)]

    def reqs():
        return [Request(rid=i, tokens=list(p), max_new=16)
                for i, p in enumerate(prompts)]

    plain, _ = serve(params, cfg, reqs(), "chunked + paged")
    # prefix cache: the first pass publishes every prompt's full blocks on
    # retirement; the second pass (same prompts, same engine) aliases them
    # and skips the cached prefill outright — still token-identical.
    cold, eng = serve(params, cfg, reqs(), "prefix cache (cold)",
                      prefix_cache=True)
    warm, _ = serve(params, cfg, reqs(), "prefix cache (warm)", engine=eng)
    assert [r.out for r in plain] == [r.out for r in cold] \
        == [r.out for r in warm], "prefix cache must be token-identical"
    assert eng.prefix_hit_pages > 0, "warm pass must alias cached pages"
    print("plain == prefix-cached (cold & warm), token for token")
    print(f"  warm pass reused {eng.prefix_hit_pages} pages / "
          f"{eng.prefix_hit_tokens} prompt tokens from the prefix cache")
    for r in plain[:4]:
        print(f"  req {r.rid:2d} | prompt len {len(r.tokens):3d} -> {r.out}")

    # int8 quantized KV: same pool geometry, ~3.2x the live tokens per byte.
    # Quantization is lossy, so on THIS random-init model (near-tie logit
    # margins) greedy tokens may flip at a few positions.  Here we assert
    # the memory win and that errors stay at zero, and report the
    # agreement.
    q8, qeng = serve(params, cfg, reqs(), "chunked + paged int8",
                     kv_dtype="int8")
    assert all(r.error is None for r in q8)
    bytes_of = lambda e: sum(b.size * b.dtype.itemsize for b in
                             jax.tree_util.tree_leaves(e.caches))
    ratio = bytes_of(qeng) / bytes_of(eng)
    assert ratio <= 0.55, f"int8 cache bytes ratio {ratio:.3f} not halved"
    agree = np.mean([a == b for rf, rq in zip(plain, q8)
                     for a, b in zip(rf.out, rq.out)])
    print(f"int8 KV cache: {ratio:.2f}x the fp cache bytes, "
          f"{agree:.0%} token agreement with fp greedy on random-init "
          f"weights")


if __name__ == "__main__":
    main()
