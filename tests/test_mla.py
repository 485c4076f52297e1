"""Latent attention (MLA) and the dropless held-expert MoE layer of
deepseek-v2-lite, at a shrunk width on the CPU: the absorbed serving form
against the plain form, the YaRN constants, the latent decode kernel
against its oracle in interpret mode, the expert shares against the
uncut layer, and the engine's refusals."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLA, get_config, shrink
from repro.core.famous import FamousConfig
from repro.kernels.decode import decode_attn
from repro.kernels.decode import ref as dec_ref
from repro.models import layers, mla, module, moe, transformer
from repro.serve.engine import Request, ServingEngine

FCFG = FamousConfig(impl="xla")


def _cfg(**over):
    return shrink(get_config("deepseek-v2-lite"), **over)


def _params(cfg, seed=0):
    return module.init_params(transformer.model_spec(cfg),
                              jax.random.PRNGKey(seed), jnp.float32)


def test_yarn_constants_as_published():
    cfg = get_config("deepseek-v2-lite")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    f = mla.inv_freq(cfg)
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # dims below the ramp (low = 10) extrapolate, above it (high = 23)
    # interpolate by the factor
    np.testing.assert_allclose(f[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], extra[23:] / 40, rtol=1e-6)
    r = (16 - 10) / 13
    assert f[16] == pytest.approx(extra[16] / 40 * r + extra[16] * (1 - r),
                                  rel=1e-6)


def test_absorbed_and_plain_forms_agree(paged_prefill):
    """Chunked prefill then decode through the paged latent pool (absorbed
    form) give the full-sequence plain form's logits."""
    cfg = _cfg()
    params = _params(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0,
                              cfg.vocab_size)
    full = transformer.forward(params, toks, cfg, remat=False)
    caches, pt = paged_prefill(params, cfg, toks[:, :11], max_seq=32)
    got = []
    clen = jnp.full((2,), 11, jnp.int32)
    for t in range(11, 20):
        lg, caches = transformer.decode_step(params, toks[:, t], caches,
                                             clen, cfg, page_table=pt)
        clen = clen + 1
        got.append(lg)
    want = full[:, 11:20]
    got = jnp.stack(got, 1)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())


def test_latent_rows_are_stored_lane_padded_with_zeros():
    cfg = _cfg()
    p = _params(cfg)["head0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, cfg.d_model))
    rows = mla.latent_rows(p, x, jnp.arange(5), cfg)
    q = mla.absorbed_queries(p, x, jnp.arange(5), cfg)
    assert rows.shape[-1] == q.shape[-1] == mla.stored_width(cfg) == 128
    assert not jnp.any(rows[..., cfg.latent_row:])
    assert not jnp.any(q[..., cfg.latent_row:])


# the latent kernel reads layer LAYER of a 3-layer stack of pools whose
# other layers hold NaN
LAYER = 1


def _pool_case(key, B, H, width, page_size, n_p, lens, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    n_pages = 1 + B * n_p
    q = jax.random.normal(ks[0], (B, 1, H, width)).astype(dtype)
    pool = jax.random.normal(ks[1], (1, n_pages, page_size, width)
                             ).astype(dtype)
    nan = jnp.full_like(pool, jnp.nan)
    pool = jnp.stack([nan, pool, nan])
    table = jax.random.permutation(ks[2], jnp.arange(1, n_pages)).reshape(
        B, n_p).astype(jnp.int32)
    return q, pool, table, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("page_size,n_p,lens", [
    (16, 40, [1, 16, 511, 512, 513, 640]),     # 32-page blocks, 2 a slot
    (8, 70, [1, 8, 511, 512, 513, 560]),       # 64-page blocks, 2 a slot
    (16, 8, [1, 17, 127, 128]),                # one block covers the table
], ids=["p16", "p8", "one_block"])
def test_latent_kernel_matches_its_oracle(page_size, n_p, lens):
    q, pool, table, lens = _pool_case(jax.random.PRNGKey(3), len(lens), 4,
                                      128, page_size, n_p, lens)
    out = decode_attn.mla_decode_attention(q, pool, table, lens, LAYER,
                                           scale=0.09, rank=96,
                                           interpret=True)
    want = dec_ref.mla_decode_reference(q, pool, table, lens, LAYER,
                                        scale=0.09, rank=96)
    assert out.shape == (len(lens), 1, 4, 96)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_latent_kernel_at_published_head_widths():
    """16 heads over 640-wide rows (rank 512 + rope 64, lane-padded)."""
    q, pool, table, lens = _pool_case(jax.random.PRNGKey(4), 3, 16, 640, 16,
                                      36, [2, 300, 576])
    scale = mla.softmax_scale(get_config("deepseek-v2-lite"))
    out = decode_attn.mla_decode_attention(q, pool, table, lens, LAYER,
                                           scale=scale, rank=512,
                                           interpret=True)
    want = dec_ref.mla_decode_reference(q, pool, table, lens, LAYER,
                                        scale=scale, rank=512)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_latent_kernel_never_reads_past_the_length():
    q, pool, table, lens = _pool_case(jax.random.PRNGKey(5), 2, 4, 128, 16,
                                      40, [100, 513], dtype=jnp.float32)
    live = np.zeros(pool.shape[2], bool)
    for b, n in enumerate(np.asarray(lens)):
        live[np.asarray(table[b, :-(-int(n) // 16)])] = True
    bad = pool.at[LAYER, 0, ~live].set(jnp.nan)
    out = decode_attn.mla_decode_attention(q, bad, table, lens, LAYER,
                                           scale=0.1, rank=96,
                                           interpret=True)
    want = dec_ref.mla_decode_reference(q, pool, table, lens, LAYER,
                                        scale=0.1, rank=96)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def _moe_case(n_experts=8, k=3):
    cfg = _cfg(num_experts=n_experts, experts_per_token=k,
               held_experts=tuple(range(n_experts)))
    spec = moe.serving_moe_spec(cfg)
    full = module.init_params(spec, jax.random.PRNGKey(6), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 5, cfg.d_model))
    return cfg, full, x


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips' shares (two experts each of eight): their held parts,
    with the shared experts counted once, are the layer with every
    expert."""
    cfg, full, x = _moe_case()
    whole, counts = moe.apply_moe_serving(full, x, cfg)
    shared = moe.layers.apply_mlp(full["shared"], x, cfg.act)
    total, rows = -3 * shared, 0
    for c in range(4):
        held = (2 * c, 2 * c + 1)
        sub = dict(full, **{n: full[n][jnp.asarray(held)]
                            for n in ("w_in", "w_gate", "w_out")})
        y, n = moe.apply_moe_serving(sub, x, dataclasses.replace(
            cfg, held_experts=held))
        total, rows = total + y, rows + int(n[:-2].sum())
        assert int(n[-2]) == 15 * 3
        assert int(n[-1]) == int((n[:-2] > 0).sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    assert rows == int(counts[:-2].sum()) == int(counts[-2]) == 15 * 3


def test_a_tokens_experts_ignore_its_batch_mates():
    """Dropless: a token's output is the same alone as in any batch, and
    a token that is not valid takes no expert row."""
    cfg, full, x = _moe_case()
    cfg = dataclasses.replace(cfg, held_experts=(1, 4, 6))
    sub = dict(full, **{n: full[n][jnp.asarray([1, 4, 6])]
                        for n in ("w_in", "w_gate", "w_out")})
    y, _ = moe.apply_moe_serving(sub, x, cfg)
    alone, _ = moe.apply_moe_serving(sub, x[1:2, 2:3], cfg)
    np.testing.assert_allclose(np.asarray(alone[0, 0]), np.asarray(y[1, 2]),
                               atol=1e-6)
    valid = jnp.zeros((3, 5), bool).at[1, 2].set(True)
    masked, n = moe.apply_moe_serving(sub, x, cfg, valid=valid)
    np.testing.assert_allclose(np.asarray(masked[1, 2]), np.asarray(y[1, 2]),
                               atol=1e-6)
    assert int(n[-2]) == 3 and int(n[:-2].sum()) <= 3


def test_grouped_matmul_kernel_matches_ragged_dot():
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    lhs = jax.random.normal(ks[0], (64, 256))
    rhs = jax.random.normal(ks[1], (4, 256, 128))
    sizes = jnp.asarray([5, 0, 17, 9], jnp.int32)
    out = gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
              tiling=moe._gmm_tiling(256, 128, 4), interpret=True)
    want = moe.grouped_matmul(lhs, rhs, sizes, "xla")
    np.testing.assert_allclose(np.asarray(out[:31]), np.asarray(want[:31]),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("kw,what", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"speculative": True}, "speculative decoding"),
], ids=["int8_kv", "speculation"])
def test_latent_attention_refuses_what_it_does_not_support(kw, what):
    cfg = _cfg()
    with pytest.raises(ValueError, match=what):
        ServingEngine(_params(cfg), cfg, FCFG, n_slots=2, max_seq=64, **kw)


def test_latent_pool_refuses_int8():
    with pytest.raises(ValueError, match="int8"):
        transformer.make_caches(_cfg(), 2, 64, jnp.float32, page_size=16,
                                n_pages=9, kv_dtype="int8")


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_latent_step_writes_only_its_rows_into_each_layer(step,
                                                          identity_layout):
    """One decode step, or one prefill chunk, through the latent pools of
    all three layers (the dense head's own pool and the two-layer stack
    the scan carries): every (layer, page, offset) it does not write is
    bit-identical after it, and the rows it writes are the right latent
    rows — decode: those the same step writes with the same history laid
    out under another page table; the chunk, at offset 0: the rows of the
    plain form's layer inputs, layer by layer — so a row written into the
    wrong page or layer, or a layer that reads another's pool, fails."""
    cfg = _cfg()
    params = _params(cfg, seed=4)
    B, ps, n_p = 2, 4, 4
    S, n_pages = n_p * ps, 1 + B * n_p
    pt = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, n_pages)).reshape(B, n_p), jnp.int32)
    paged = transformer.make_caches(cfg, B, S, jnp.float32, page_size=ps,
                                    n_pages=n_pages)
    leaves, treedef = jax.tree_util.tree_flatten(paged)
    paged = treedef.unflatten([
        jax.random.normal(k, a.shape) for a, k in
        zip(leaves, jax.random.split(jax.random.PRNGKey(1), len(leaves)))])
    # every pool as (layers, n_pages, ps, row): the head's, then the stack's
    stack = lambda c: jnp.concatenate(                 # noqa: E731
        [c["head0"]["c"], c["blocks"]["pos0"]["c"][:, 0]])
    if step == "decode":
        toks = jnp.asarray([3, 7], jnp.int32)
        clen = jnp.asarray([5, 10], jnp.int32)
        act = jnp.ones((B,), bool)
        ident = 1 + jnp.arange(B * n_p, dtype=jnp.int32).reshape(B, n_p)
        moved = {"head0": {"c": identity_layout(
                     paged["head0"]["c"][None], pt)[0]},
                 "blocks": {"pos0": {"c": identity_layout(
                     paged["blocks"]["pos0"]["c"], pt)}}}
        _, new = transformer.decode_step(params, toks, paged, clen, cfg,
                                         FCFG, page_table=pt, active=act)
        _, ref = transformer.decode_step(params, toks, moved, clen, cfg,
                                         FCFG, page_table=ident, active=act)
        rows = [(b, int(n)) for b, n in enumerate(clen)]
        ref_rows = {(b, p): stack(ref)[:, int(ident[b, p // ps]), p % ps]
                    for b, p in rows}
    else:
        slot, C = 1, 8
        toks = jnp.asarray([[5, 9, 2, 11, 4, 8, 1, 6]], jnp.int32)
        new = transformer.prefill_chunk(
            params, toks, paged, jnp.int32(slot), jnp.int32(0), jnp.int32(C),
            cfg, FCFG, page_table=pt)
        # the plain form, layer by layer: each layer's rows of its input
        x = layers.embed_lookup(params["embed"], toks, jnp.float32)
        pos = jnp.arange(C)
        want = []
        for p in [params["head0"]] + [
                jax.tree_util.tree_map(lambda a: a[u], params["blocks"]["pos0"])
                for u in range(cfg.num_units)]:
            h = layers.apply_norm(p["ln1"], x, cfg.norm)
            want.append(mla.latent_rows(p["attn"], h, pos, cfg)[0])
            x = transformer.apply_block(MLA, p, x, cfg, FCFG)
        rows = [(slot, p) for p in range(C)]
        ref_rows = {(slot, p): jnp.stack(want)[:, p] for p in range(C)}
    old, new = stack(paged), stack(new)              # (3, n_pages, ps, row)
    written = np.zeros(old.shape[:3], bool)
    for b, p in rows:
        written[:, int(pt[b, p // ps]), p % ps] = True
    np.testing.assert_array_equal(np.asarray(new)[~written],
                                  np.asarray(old)[~written])
    for b, p in rows:
        np.testing.assert_allclose(new[:, int(pt[b, p // ps]), p % ps],
                                   ref_rows[b, p], rtol=1e-5, atol=1e-5)


def test_engine_serves_through_the_paged_latent_pool():
    """Paged latent pool, prefix cache and chunked prefill: requests that
    share a head hit the prefix cache, and every token agrees with the
    greedy tokens of the plain full-sequence forward."""
    cfg = _cfg()
    params = _params(cfg, seed=3)
    params = jax.tree_util.tree_map(lambda a: a * 4.0, params)  # no near-ties
    rng = np.random.default_rng(0)
    head = list(rng.integers(0, cfg.vocab_size, 32))
    prompts = [head + list(rng.integers(0, cfg.vocab_size, n))
               for n in (3, 11)] + [list(rng.integers(0, cfg.vocab_size, 9))]
    eng = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64, chunk=16,
                        page_size=8, prefix_cache=True)
    reqs = [Request(rid=i, tokens=p, max_new=5)
            for i, p in enumerate(prompts)]
    # the first request publishes the head's pages on retiring
    done = eng.run(reqs[:1]) + eng.run(reqs[1:])
    assert eng.prefix_cache_active and eng.prefix_hit_tokens >= 32
    assert eng.compilations["prefill"] == eng.compilations["decode"] == 1
    for r in done:
        seq = r.tokens + r.out[:-1]
        logits = transformer.forward(params, jnp.asarray([seq]), cfg,
                                     remat=False)[0]
        want = np.asarray(logits[len(r.tokens) - 1:].argmax(-1))
        assert r.out == want.tolist(), r.rid
