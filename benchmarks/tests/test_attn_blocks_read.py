"""``attn_blocks_read_pct.hybrid`` on two scrapes in the form the runner
keeps them: a number where the program exports
``stpu_attn_blocks_read_total``, ``None`` where it does not (the parent's
scrape of the same cell has every other series) and for every other
family."""
import importlib.util
import pathlib

import pytest

from benchmarks import cells

CELL = "phi4flash-fewshot-reason-steady"
# Two scrapes 3 s apart, sized like cell 6's traced window (115 decode
# steps, 23.6 % of the gather's blocks: my chip run, PR 37, call A).
WITH = [(10.0, {("stpu_engine_steps_total", (("kind", "decode"),)): 1510.0,
                ("stpu_engine_steps_total", (("kind", "prefill"),)): 960.0,
                ("stpu_attn_blocks_read_total", ()): 5_290_000.0}),
        (13.0, {("stpu_engine_steps_total", (("kind", "decode"),)): 1625.0,
                ("stpu_engine_steps_total", (("kind", "prefill"),)): 1027.0,
                ("stpu_attn_blocks_read_total", ()): 5_693_512.0})]
WITHOUT = [(t, {k: v for k, v in s.items()
                if k[0] != "stpu_attn_blocks_read_total"})
           for t, s in WITH]


def _metric():
    path = (pathlib.Path(cells.ROOT) / "layer_metrics"
            / "attn_blocks_read_pct.hybrid.py")
    spec = importlib.util.spec_from_file_location("attn_blocks_read", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(samples, family="phi4flash"):
    cfg = dict(cells.load_cell(CELL)["config"], family=family)
    return {"config": cfg, "samples": samples, "t0": 9.0, "t1": 14.0,
            "child": {"kv": {"slots": 64, "chunk": 64, "max_seq": 1280}}}


def test_the_gathers_blocks_by_hand():
    mod = _metric()
    cfg = cells.load_cell(CELL)["config"]
    # Eight window layers of 9 blocks, the full layer's 20 read by it and
    # by seven cross layers.
    assert mod.blocks_spanned(cfg, 64, 1280) == 8 * 9 + 8 * 20 == 232
    # A table shorter than a window holds no more than its span.
    assert mod.blocks_spanned(cfg, 64, 256) == 8 * 4 + 8 * 4


def test_a_scrape_with_the_counter_reads_a_share():
    got = _metric().compute(_run(WITH))
    assert got == pytest.approx(100 * 403_512 / (115 * 64 * 232))
    assert 23 < got < 24


@pytest.mark.parametrize("run", [
    _run(WITHOUT), _run(WITH, family="deepseek"), _run(WITH[:1])],
    ids=["the parent's scrape", "another family", "one scrape"])
def test_nothing_to_read_is_none(run):
    assert _metric().compute(run) is None
