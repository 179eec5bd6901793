"""The schedule builder: pinned for one seed, and the same work for
every seed."""
import json
import pathlib

from benchmarks import loadgen

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
CHAT = json.loads((TRAFFIC / "chat-steady.json").read_text())
# A closed loop: 64 callers, chat-steady's lengths, nothing shared (the
# mix ISSUE 24's four-chip cell would use; PERF.md, section 7).
OFFLINE = {"loop": "closed", "clients": 64, "pool": 1024, "lead_in_s": 12,
           "prompt_tokens": CHAT["prompt_tokens"],
           "output_tokens": CHAT["output_tokens"], "temperature": 0.0}


def build(seed, mix=CHAT, rate=6.0):
    return loadgen.build_schedule(mix, rate=rate, seconds=40.0, seed=seed,
                                  vocab=32768)


def test_digest_is_pinned_for_one_seed():
    sched = build(3000000019)
    assert len(sched) == 36 + 240
    assert loadgen.schedule_digest(sched) == PINNED
    assert loadgen.schedule_digest(build(3000000019)) == PINNED
    assert loadgen.schedule_digest(build(3000000020)) != PINNED


def test_every_seed_does_the_same_work_in_the_same_order():
    a, b = build(1), build(2 ** 31 + 11)
    assert [(len(r.prompt), r.max_tokens, r.due) for r in a] == \
        [(len(r.prompt), r.max_tokens, r.due) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_without_an_order_seed_the_order_is_the_seeds():
    free = {k: v for k, v in CHAT.items() if k != "order_seed"}
    a, b = build(1, free), build(2, free)
    for pick in (lambda r: len(r.prompt), lambda r: r.max_tokens):
        assert sorted(map(pick, a)) == sorted(map(pick, b))

    def gaps(s):
        due = [r.due for r in s[36:]] + [40.0]
        return sorted(round(y - x, 9) for x, y in zip(due, due[1:]))
    assert gaps(a) == gaps(b)
    assert [r.due for r in a] != [r.due for r in b]


def test_lengths_keep_to_the_clips_and_share_prefixes():
    sched = build(7)
    measured = [r for r in sched if r.measured]
    assert all(16 <= len(r.prompt) <= 1024 for r in sched)
    assert all(8 <= r.max_tokens <= 256 for r in sched)
    assert min(r.due for r in measured) == 0.0
    assert max(r.due for r in measured) < 40.0
    assert min(r.due for r in sched) == -6.0
    long = [r for r in measured if len(r.prompt) >= 136]
    heads = {r.prompt[:128] for r in long}
    assert len(heads) == 4
    assert len({r.prompt[128:136] for r in long}) == len(long)
    lens = sorted(len(r.prompt) for r in measured)
    assert 240 <= lens[len(lens) // 2] <= 272


def test_closed_loop_pool_has_no_times_and_no_shared_prefix():
    sched = build(3, OFFLINE, rate=None)
    assert len(sched) == 1024 and all(r.due is None for r in sched)
    assert len({r.prompt[:16] for r in sched}) == len(sched)


def test_metrics_text_and_histogram_quantile():
    text = ('# HELP x\nstpu_engine_slots_occupied 7\n'
            'h_bucket{le="0.1"} 10\nh_bucket{le="0.2"} 30\n'
            'h_bucket{le="+Inf"} 30\n')
    parsed = loadgen.parse_metrics(text)
    assert parsed[("stpu_engine_slots_occupied", ())] == 7.0
    empty = {k: 0.0 for k in parsed}
    bounds, cum = loadgen.histogram_delta(
        [(0.0, empty), (1.0, parsed)], "h", 0.0, 1.0)
    assert bounds[:2] == [0.1, 0.2] and cum == [10.0, 30.0, 30.0]
    # Rank 15 of 30 lies a quarter into the second bucket.
    assert abs(loadgen.histogram_quantile(bounds, cum, 0.5) - 0.125) < 1e-9


PINNED = "16a00abd805afbf6fdace215601617aab5f0395cee5764693dd4cfa63c5c6d82"
