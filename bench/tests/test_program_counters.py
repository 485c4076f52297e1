"""The program's own counters against the harness's step log, on a shrunk
engine on the CPU: ``repro_decode_launches_total`` and
``repro_admitted_prompt_tokens_total`` are the in-program sources of
``decode_batch_mean`` and ``prefix_hit_share``."""
from bench import harness, traffic as traffic_lib
from bench.tests import tiny
from repro.obs.runtime import Observer

SEED = 2 ** 31 + 97


def test_program_counters_agree_with_the_step_log():
    cell = tiny.cell("docs")
    eng = harness.build_engine(cell, SEED)
    tr = traffic_lib.Traffic(cell.mix, SEED, cell.dims["vocab"])
    runner = harness.Runner(eng, tr)
    runner.serve_now([tr.warmup(eng.chunk)] + tr.priming(eng.pcfg.page_size))
    obs = Observer()                 # counts from the window on
    eng.obs = eng.sched.obs = obs
    runner.start(harness.clock())
    runner.drive(harness.clock() + 1.5)
    m = obs.metrics
    launches = [len(s.decode_lens) for s in runner.steps if s.decode_lens]
    assert launches
    assert m.get("repro_decode_launches_total").value() == len(launches)
    assert m.get("repro_tokens_generated_total").value() == sum(launches)
    assert m.get("repro_admitted_prompt_tokens_total").value() \
        == sum(n for _, n, _ in runner.admissions) > 0
    hits = sum(h for _, _, h in runner.admissions)
    assert m.get("repro_prefix_tokens_saved_total").value() == hits > 0
