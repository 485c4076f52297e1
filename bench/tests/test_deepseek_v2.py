"""DeepSeek-V2-Lite's cell on the CPU at a shrunk width: its weights make
the program's parameter tree, the plain reference agrees with the
engine's prefill, paged decode and prefix-aliased pages (and an fp8 step
does not), a whole run through the harness is judged correct, and the
latent kernel's cost function and the cell's readers read what they
should."""
import dataclasses
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import deepseek_v2_reference as R
from bench import deepseek_v2_weights as W
from bench import harness, mla_costs, peaks, trace
from bench.tests import tiny

SEED = 2 ** 33 + 17
CONF = json.loads((tiny.ROOT / "bench/configs/deepseek-v2-lite.json")
                  .read_text())
TINY = dict(CONF, name="tiny-v2", hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_attention_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_hidden_layers=3, vocab_size=256, n_routed_experts=5,
            held_experts=[0, 2, 3, 5, 7], published={"n_routed_experts": 8},
            num_experts_per_tok=3,
            engine={"n_slots": 4, "max_seq": 128, "page_size": 16,
                    "chunk": 16, "token_budget": 0, "prefix_cache": True,
                    "n_pages": 33})
MIX = {"loop": "closed", "clients": 4, "ramp_s": 0.3, "block": 8,
       "order_seed": 3, "documents": {"count": 2, "length": 48},
       "prompt": {"dist": "uniform", "min": 4, "max": 12},
       "output": {"dist": "uniform", "min": 4, "max": 12}}
# f32 program against the f32 reference: rounding only; the fp8 control
# reads two orders of magnitude above it
LIMIT = 1e-3


def _cell(**check):
    spec = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    mine = lambda m: "deepseek-v2-lite.long-doc" in m.get(   # noqa: E731
        "workloads", ["deepseek-v2-lite.long-doc"])
    return harness.Cell(
        name="tiny-v2.long-doc", chips=1, config=dict(TINY), mix=MIX,
        check=dict(s_pad=128, b_pad=4, n_score=64, limit=LIMIT, **check),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)])


def test_the_published_file_keeps_the_router_and_the_widths():
    m = W.model_dims(CONF)
    assert (m["experts"], m["top_k"], len(m["held"])) == (64, 6, 16)
    assert m["held"] == tuple(range(16))
    cfg = W.program_config(CONF)
    assert (cfg.num_layers, cfg.d_model, cfg.kv_lora_rank, cfg.moe_d_ff,
            cfg.d_ff, cfg.first_dense_layers) == (27, 2048, 512, 1408,
                                                  10944, 1)
    # every number of the published config is kept, but the held count
    assert cfg.param_count() == 4_910_345_728


def test_weights_make_the_programs_tree():
    from repro.models import module, transformer
    cell = _cell()
    cfg = cell.weights.program_config(cell.config)
    params = cell.weights.serving_params(cell.dims, jnp.float32)(
        jnp.asarray(W.seed_words(SEED)))
    want = module.param_shapes(transformer.model_spec(cfg), jnp.float32)
    assert harness._layout(params) == harness._layout(want)


def test_an_expert_draws_alike_alone_and_in_its_layer():
    m = W.model_dims(TINY)
    seeds = jnp.asarray(W.seed_words(SEED))
    whole = W.draw(seeds, m, "e_up", jnp.float32, 2)
    for e in (0, 5, 7):
        one = W.draw_expert(seeds, m, "e_up", jnp.float32, 2, e)
        assert np.array_equal(np.asarray(whole[e]), np.asarray(one))
    held = W._stacked(seeds, m, "e_up", jnp.float32, 1, 2, m["held"])
    assert np.array_equal(np.asarray(held[1, 3]), np.asarray(whole[5]))


@pytest.fixture(scope="module")
def served():
    """Three requests through the engine, two sharing a 40-token head
    (so the second reads prefix-aliased pages), with every decode step's
    logits."""
    from repro.serve.engine import Request
    cell = _cell()
    eng = harness.build_engine(cell, SEED)
    got, decode = [], eng._decode

    def spy(*a, **k):
        logits, caches = decode(*a, **k)
        got.append((np.asarray(k["active"]), np.asarray(logits)))
        return logits, caches

    eng._decode = spy
    rng = np.random.default_rng(0)
    head = rng.integers(0, 256, 40).tolist()
    reqs = [Request(rid=0, tokens=head + [5, 9], max_new=6)]
    eng.run(reqs)
    more = [Request(rid=1, tokens=head + rng.integers(0, 256, 7).tolist(),
                    max_new=6),
            Request(rid=2, tokens=rng.integers(0, 256, 23).tolist(),
                    max_new=6)]
    eng.run(more)
    assert eng.prefix_hit_tokens >= 32
    return cell, reqs + more, got


def _rows(cell, reqs, control=None):
    toks = np.zeros((len(reqs), 64), np.int32)
    rows = []
    for b, r in enumerate(reqs):
        seq = r.tokens + r.out[:-1]
        toks[b, :len(seq)] = seq
        rows += [b * 64 + len(r.tokens) - 1 + j for j in range(len(r.out))]
    return np.asarray(R.logits_at(cell.dims, SEED, toks, np.array(rows),
                                  jnp.float32, control=control))


def _program(reqs, got):
    # one slot at a time: request 0 alone, then 1 and 2 in slots 0 and 1
    first = [lg[0] for act, lg in got if act[0]]
    out = first[:len(reqs[0].out)]
    rest = got[len(out):]
    for slot, r in ((0, reqs[1]), (1, reqs[2])):
        out += [lg[slot] for act, lg in rest if act[slot]][:len(r.out)]
    return np.stack(out)


def test_reference_matches_prefill_decode_and_aliased_pages(served):
    cell, reqs, got = served
    ref = _rows(cell, reqs)
    prog = _program(reqs, got)
    scale = np.abs(ref).max()
    assert np.abs(prog - ref).max() <= 1e-4 * scale
    assert (ref.argmax(-1) == [t for r in reqs for t in r.out]).all()
    # a bf16 -> fp8 step moves the logits far past that tolerance
    fp8 = _rows(cell, reqs, control="fp8")
    assert np.abs(fp8 - ref).max() > 100 * 1e-4 * scale


def test_a_whole_run_is_judged_correct_and_the_fp8_control_is_not():
    res = harness.run(_cell(), SEED, 1.5, False, time.perf_counter(),
                      kind="TPU v5 lite", controls=("fp8",))
    chk = res["checks"]
    assert res["correct"] and chk["logit_gap"]["tokens"] > 0
    assert chk["logit_gap"]["value"] <= LIMIT
    assert chk["control_fp8_logit_gap"]["correct"] is False
    assert {"output_tok_s", "setup_s"} <= set(res["metrics"])


def test_latent_decode_cost_by_hand():
    # 16 heads over rank 512 + rope 64: per entry 2 * 16 * (576 + 512)
    # FLOPs and one 576-wide bf16 row; two slots of 100 and 1,000 entries,
    # their queries (576 + 512 wide per head) read and written once
    flops, nbytes = mla_costs.mla_decode_call([100, 1000], heads=16,
                                              rank=512, rope=64)
    assert flops == 1100 * 34_816 == 38_297_600
    assert nbytes == 1100 * 1152 + 2 * 16 * 1088 * 2 == 1_336_832


def _summary():
    """Two decode launches of 10 ms with a latent kernel of 2 ms and a
    grouped matmul of 3 ms in each, and a prefill chunk whose grouped
    matmul the readers must not count."""
    ops, mods = [], []
    for i, t0 in enumerate((0.0, 0.02)):
        mods.append(("jit_decode_step", t0, 0.010))
        ops += [("mla_decode_attention", "jit_decode_step", None, t0, 0.002),
                ("gmm", "jit_decode_step", None, t0 + 0.003, 0.003)]
    mods.append(("jit_prefill_chunk", 0.04, 0.02))
    ops.append(("gmm", "jit_prefill_chunk", None, 0.041, 0.005))
    return trace.Summary(host_t0=0.0, host_t1=0.1, busy=[[0.0, 0.06]],
                         ops=ops, modules=mods, host=[])


def _run(tsum, dims):
    steps = [harness.StepLog(0.01, [], [1000, 3000]),
             harness.StepLog(0.03, [], [1001, 3001])]
    return harness.RunData(cell=None, t0=0.0, t1=0.1, recs=[], steps=steps,
                           dims=dims, peak=peaks.peaks("TPU v5 lite"),
                           setup_s=1.0, lags=[], admissions=[], trace=tsum)


def test_the_cells_readers_on_a_synthetic_trace():
    dims = W.model_dims(CONF)
    run = _run(_summary(), dims)
    assert harness.metric_reader("decode_step_ms.mla")(run) == \
        pytest.approx(10.0)
    assert harness.metric_reader("moe_ffn_ms")(run) == pytest.approx(3.0)
    least = [27 * max(f / 197e12, b / 819e9) for f, b in (
        mla_costs.mla_decode_call(s.decode_lens, heads=16, rank=512, rope=64)
        for s in run.steps)]
    # mean least time per launch, times the 2 launches, over 4 ms of kernel
    want = 100.0 * sum(least) / 2 * 2 / 0.004
    assert harness.metric_reader("mla_decode_roofline")(run) == \
        pytest.approx(want)


def test_the_readers_find_nothing_where_the_kernels_are_absent():
    """A dense cell's trace (the parent, or another configuration): no
    latent kernel and no grouped matmul, so no reading, and no error."""
    t = _summary()
    t = dataclasses.replace(t, ops=[o for o in t.ops
                                    if o[0] not in ("gmm",
                                                    "mla_decode_attention")])
    dense = {"d": 3584, "h": 28, "kv": 4, "dh": 128, "layers": 28}
    assert harness.metric_reader("mla_decode_roofline")(_run(t, dense)) \
        is None
    assert harness.metric_reader("moe_ffn_ms")(_run(t, dense)) is None
    assert harness.metric_reader("mla_decode_roofline")(
        _run(None, W.model_dims(CONF))) is None
