"""``attn_blocks_read_pct.mla`` on two scrapes in the form the runner
keeps them: a number where the program counts
``stpu_attn_blocks_read_total``, ``None`` where it does not (the series
absent, or at 0 as the parent's engine exports it for phi4flash) and for
every other family."""
import importlib.util
import pathlib

import pytest

from benchmarks import cells

CELL = "deepseekv3-reason-steady"
# Two scrapes 3 s apart, sized like cell 4's traced window at its ~11
# decoding slots: 350 decode steps, ~7 blocks a slot in each of 5 layers.
WITH = [(10.0, {("stpu_engine_steps_total", (("kind", "decode"),)): 4000.0,
                ("stpu_engine_steps_total", (("kind", "prefill"),)): 700.0,
                ("stpu_attn_blocks_read_total", ()): 1_540_000.0}),
        (13.0, {("stpu_engine_steps_total", (("kind", "decode"),)): 4350.0,
                ("stpu_engine_steps_total", (("kind", "prefill"),)): 765.0,
                ("stpu_attn_blocks_read_total", ()): 1_674_750.0})]
WITHOUT = [(t, {k: v for k, v in s.items()
                if k[0] != "stpu_attn_blocks_read_total"})
           for t, s in WITH]
# The parent's engine exports the series for phi4flash's kernel, and its
# deepseek step never adds to it.
AT_ZERO = [(t, {**s, ("stpu_attn_blocks_read_total", ()): 0.0})
           for t, s in WITHOUT]


def _metric():
    path = (pathlib.Path(cells.ROOT) / "layer_metrics"
            / "attn_blocks_read_pct.mla.py")
    spec = importlib.util.spec_from_file_location("attn_blocks_read_mla",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(samples, family="deepseek"):
    cfg = dict(cells.load_cell(CELL)["config"], family=family)
    return {"config": cfg, "samples": samples, "t0": 9.0, "t1": 14.0,
            "child": {"kv": {"slots": 64, "chunk": 64, "max_seq": 1280}}}


def test_a_scrape_with_the_counter_reads_a_share():
    # 134,750 blocks over 350 steps x 64 slots x 5 layers x 20 blocks.
    got = _metric().compute(_run(WITH))
    assert got == pytest.approx(100 * 134_750 / (350 * 64 * 5 * 20))
    assert 6 < got < 6.1


@pytest.mark.parametrize("run", [
    _run(WITHOUT), _run(AT_ZERO), _run(WITH, family="phi4flash"),
    _run(WITH[:1])],
    ids=["no series", "the parent's series at 0", "another family",
         "one scrape"])
def test_nothing_to_read_is_none(run):
    assert _metric().compute(run) is None
