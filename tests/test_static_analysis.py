"""The unified static-analysis framework (`stpu check`).

One tier-1 test replaces the four scattered lint tests
(test_observability / test_fault_tolerance / test_sharded_replica /
test_checkpoint): the whole rule suite runs over ``skypilot_tpu/`` in
one AST walk per file and must be clean. Every rule also gets a
good/bad/noqa'd fixture corpus, the ``--json`` schema is pinned, and
the env-knob table embedded in docs/static-analysis.md is asserted
byte-identical to ``env_contract.render_markdown_table()`` so the doc
can never drift from the registry.
"""
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest
from click.testing import CliRunner

from skypilot_tpu import analysis
from skypilot_tpu.utils import env_contract

REPO = pathlib.Path(__file__).resolve().parent.parent


def _write(tmp_path: pathlib.Path, rel: str, body: str) -> pathlib.Path:
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(body))
    return path


def _run(tmp_path, rule):
    """Run ONE rule over the fixture tree; findings keyed by rel:line."""
    findings = analysis.run_check(paths=[tmp_path], rules=[rule])
    return findings


def _lines(findings, rel):
    return sorted(f.line for f in findings if f.path == rel)


# ================================================= tier-1: repo clean
def test_repo_clean_all_rules():
    """`stpu check` over skypilot_tpu/ is clean across ALL rules —
    including the three TPU-correctness analyzers (donation,
    host-sync, env contract). This is THE lint gate; a finding here is
    a real bug or a site that needs an explained noqa."""
    findings = analysis.run_check()
    assert findings == [], "\n".join(f.render() for f in findings)
    # All seven+ advertised rules actually ran (registry intact).
    ids = {r.id for r in analysis.all_rules()}
    assert {"stpu-wallclock", "stpu-span-leak", "stpu-except",
            "stpu-atomic", "stpu-collective", "stpu-donation",
            "stpu-host-sync", "stpu-env", "stpu-armed-guard"} <= ids


# ================================================= suppression grammar
def test_noqa_reason_mandatory(tmp_path):
    """The unified grammar: `# noqa: stpu-<rule> <reason>` suppresses;
    a marker with no (or a too-short) reason does NOT."""
    _write(tmp_path, "probe.py", """\
        import time
        a = time.time() - t0
        b = time.time() - t1  # noqa: stpu-wallclock
        c = time.time() - t2  # noqa: stpu-wallclock persisted stamp from another boot
        """)
    findings = _run(tmp_path, "stpu-wallclock")
    assert _lines(findings, "probe.py") == [2, 3]
    missing = [f for f in findings if f.line == 3]
    assert "reason is missing" in missing[0].message


def test_noqa_multi_rule(tmp_path):
    """One line can suppress several rules: `# noqa: stpu-a, stpu-b
    <reason>` — and a rule NOT named on the line still fires."""
    _write(tmp_path, "serve/probe.py", """\
        import time
        from jax import lax
        x = lax.psum(time.time() - t0, 'tp')  # noqa: stpu-collective, stpu-wallclock both exercised by this fixture
        y = lax.psum(1, 'tp')  # noqa: stpu-wallclock wrong rule named
        """)
    col = _run(tmp_path, "stpu-collective")
    assert _lines(col, "serve/probe.py") == [4]
    assert _run(tmp_path, "stpu-wallclock") == []


# ================================================= ported rules corpus
def test_wallclock_rule(tmp_path):
    _write(tmp_path, "good.py", """\
        import time
        t0 = time.perf_counter()
        dur = time.perf_counter() - t0
        stamp = time.time()
        """)
    _write(tmp_path, "bad.py", """\
        import time
        dur = time.time() - t0
        """)
    findings = _run(tmp_path, "stpu-wallclock")
    assert _lines(findings, "bad.py") == [2]
    assert _lines(findings, "good.py") == []


def test_span_leak_rule(tmp_path):
    _write(tmp_path, "spans.py", """\
        from skypilot_tpu.observability import tracing
        def good_with():
            with tracing.start_span('a') as s:
                s.event('e')
        def good_assign():
            span = tracing.start_span('b')
            try:
                pass
            finally:
                span.end()
        def good_nested_closer():
            span = tracing.start_span('c')
            def finish():
                span.end(status='ok')
            finish()
        def bad_returned():
            return tracing.start_span('d')
        def bad_dropped():
            tracing.start_span('e')
        def bad_never_ended():
            leak = tracing.start_span('f')
            leak.event('x')
        def noqad():
            return tracing.start_span('g')  # noqa: stpu-span-leak caller owns the end()
        """)
    findings = _run(tmp_path, "stpu-span-leak")
    assert _lines(findings, "spans.py") == [17, 19, 21]


def test_except_rule(tmp_path):
    _write(tmp_path, "serve/bad.py", """\
        try:
            x = 1
        except Exception:
            pass
        try:
            y = 1
        except:
            pass
        try:
            z = 1
        except ValueError:
            pass
        """)
    _write(tmp_path, "serve/ok.py", """\
        try:
            x = 1
        except Exception:  # noqa: stpu-except best-effort probe, failure means no data
            pass
        """)
    _write(tmp_path, "elsewhere/bad.py",
           "try:\n    x = 1\nexcept Exception:\n    pass\n")
    findings = _run(tmp_path, "stpu-except")
    assert _lines(findings, "serve/bad.py") == [3, 7]
    assert _lines(findings, "serve/ok.py") == []
    # Only the control-plane dirs are in scope.
    assert _lines(findings, "elsewhere/bad.py") == []


def test_atomic_rule(tmp_path):
    _write(tmp_path, "train/checkpoint.py", """\
        import os, pathlib
        def write_state(p, q):
            with open(p, "w") as f:
                f.write("x")
            pathlib.Path(q).write_text("y")
            fd = os.open(p, os.O_WRONLY)
            open(p).read()
            with open(p, "rb") as f:
                f.read()
        def atomic_write_bytes(path, data):
            fd = os.open(path, os.O_WRONLY | os.O_CREAT)
            os.write(fd, data)
        def scratch(p):
            open(p, "w").write("tmp")  # noqa: stpu-atomic scratch file, rebuilt on every boot
        """)
    findings = _run(tmp_path, "stpu-atomic")
    assert _lines(findings, "train/checkpoint.py") == [3, 5, 6]


def test_collective_rule(tmp_path):
    _write(tmp_path, "serve/bad.py", """\
        import jax
        def f(x):
            return jax.lax.psum(x, 'tp')
        """)
    _write(tmp_path, "serve/ok.py", """\
        def local(x):
            psum = 3
            return psum
        """)
    _write(tmp_path, "serve/lazy.py", """\
        from jax.lax import psum
        def f(x):
            return psum(x, 'tp')  # noqa: stpu-collective
        """)
    findings = _run(tmp_path, "stpu-collective")
    assert _lines(findings, "serve/bad.py") == [3]
    assert _lines(findings, "serve/ok.py") == []
    lazy = [f for f in findings if f.path == "serve/lazy.py"]
    assert len(lazy) == 1 and "reason is missing" in lazy[0].message


# ================================================= new TPU analyzers
DONATION_FIXTURE = """\
    import functools
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(tokens, cache):
        cache = cache.at[0].set(tokens)
        return tokens + 1, cache

    @functools.partial(jax.jit, donate_argnums=(0,))
    def dead_end(cache):
        return jnp.zeros(3)

    def bad_use_after_donate(tokens, cache):
        logits, _ = step(tokens, cache)
        return cache[0]

    def bad_loop_no_rebind(tokens, cache):
        for _ in range(4):
            logits, _ = step(tokens, cache)
        return logits

    def good_rebinds(tokens, cache):
        logits, cache = step(tokens, cache)
        logits, cache = step(logits, cache)
        return logits, cache

    def good_goes_dead(tokens, cache):
        logits, _ = step(tokens, cache)
        return logits

    def noqad(tokens, cache):
        logits, _ = step(tokens, cache)
        return cache[0]  # noqa: stpu-donation CPU-only diagnostic path, never runs on TPU
    """


def test_donation_rule_seeded_fixture(tmp_path):
    """Acceptance: the donation analyzer catches a seeded
    use-after-donate (and the no-output-alias callee trap), while the
    engine's rebind convention passes."""
    _write(tmp_path, "donation.py", DONATION_FIXTURE)
    findings = _run(tmp_path, "stpu-donation")
    lines = _lines(findings, "donation.py")
    # 11: dead_end's donated param aliases no output;
    # 16: read-after-donate; 20: donating call in a loop, no rebind.
    assert lines == [11, 16, 20], [f.render() for f in findings]
    by_line = {f.line: f.message for f in findings}
    assert "aliases no output" in by_line[11]
    assert "read after being donated" in by_line[16]
    assert "inside a loop" in by_line[20]


def test_donation_rule_fresh_buffer_per_iteration(tmp_path):
    """A loop that stores a FRESH buffer before each donating call is
    clean — the back-edge read sees the new buffer, not the donated
    one."""
    _write(tmp_path, "fresh.py", """\
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(b, cache):
            return b, cache

        def per_batch(batches, init_cache):
            for b in batches:
                cache = init_cache(b)
                out, _ = step(b, cache)
            return out
        """)
    assert _run(tmp_path, "stpu-donation") == []


def test_donation_rule_covers_paged_entry_points():
    """The analyzer SEES the engine's entry points: the three serving
    programs and the host-tier restore are ALL the donators of
    decode_engine.py, each with the pool donated — so a future
    use-after-donate of the pool fails the gate."""
    from skypilot_tpu.analysis import rules_donation
    src = REPO / "skypilot_tpu" / "serve" / "decode_engine.py"
    ctx = analysis.core.FileContext(src, "serve/decode_engine.py")
    donators = {d.name: d
                for d in rules_donation._collect_donators(ctx)
                if d.name}
    assert set(donators) == {"_paged_prefill_chunk", "_paged_step",
                             "_paged_spec_step", "_host_restore_block"}
    for name, donator in donators.items():
        assert "cache" in donator.donated_params(), name


def test_donation_rule_paged_block_table_fixture(tmp_path):
    """The paged calling shape: the pool donated through a block-table
    call with extra (table / static-window) operands. Rebinding from
    the return is clean; reading the pool after donating it — or
    donating in the decode loop without rebind — is flagged. The
    TABLE is not donated, so reading it after the call stays clean."""
    _write(tmp_path, "paged.py", """\
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnums=(0, 4),
                           donate_argnums=(1,))
        def paged_step(cfg, pool, toks, table, window):
            pool = pool.at[table[0]].set(toks)
            return toks + 1, pool

        def good_engine_loop(cfg, pool, toks, table):
            for _ in range(8):
                toks, pool = paged_step(cfg, pool, toks, table, 64)
                probe = table[0]        # table NOT donated: fine
            return toks, pool

        def bad_pool_read(cfg, pool, toks, table):
            nxt, _ = paged_step(cfg, pool, toks, table, 64)
            return pool[0]

        def bad_loop_no_rebind(cfg, pool, toks, table):
            for _ in range(8):
                nxt, _ = paged_step(cfg, pool, toks, table, 64)
            return nxt
        """)
    findings = _run(tmp_path, "stpu-donation")
    lines = _lines(findings, "paged.py")
    assert lines == [19, 23], [f.render() for f in findings]


def test_donation_rule_self_attribute_paths(tmp_path):
    """Dotted donation targets (`self._cache`) are tracked: rebinding
    from the return is clean, a later read is use-after-donate —
    exactly the decode-engine convention."""
    _write(tmp_path, "engine.py", """\
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _engine_step(toks, cache):
            return toks, cache

        class Engine:
            def good(self, toks):
                toks, self._cache = _engine_step(toks, self._cache)
                return toks
            def bad(self, toks):
                toks2, _ = _engine_step(toks, self._cache)
                return self._cache
        """)
    findings = _run(tmp_path, "stpu-donation")
    assert _lines(findings, "engine.py") == [14]


def test_host_sync_rule(tmp_path):
    _write(tmp_path, "serve/decode_engine.py", """\
        import functools
        import jax
        import jax.numpy as jnp
        import numpy as np

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _decode_step(tokens, cache):
            return tokens + 1, cache

        def engine_loop(tokens, cache):
            while True:
                tokens, cache = _decode_step(tokens, cache)
                t = tokens.item()
                host = np.asarray(tokens)
                print(tokens)
                fetched = jax.device_get(tokens)
                ok = float(fetched[0])
                temp = float("0.7")

        def hot_helper(tokens):
            val = jnp.sum(tokens)
            return float(val)

        def cold_helper(request):
            return float(request["temperature"])
        """)
    findings = _run(tmp_path, "stpu-host-sync")
    lines = _lines(findings, "serve/decode_engine.py")
    # .item(), np.asarray(device), print(device) flagged; the
    # device_get fetch un-taints, so the post-fetch float() and host
    # scalars (cold_helper's temperature) never trip the rule.
    assert 13 in lines and 14 in lines and 15 in lines
    assert 17 not in lines and 18 not in lines and 25 not in lines
    # Reachability scope: hot_helper is never called from the per-token
    # path, so its float(jnp.sum(...)) is out of scope by design.
    assert 22 not in lines
    # The rule only targets the two engine files: same sync pattern in
    # another serve/ module is out of scope.
    _write(tmp_path, "serve/other.py", "def f(a):\n    return a.item()\n")
    findings = _run(tmp_path, "stpu-host-sync")
    assert _lines(findings, "serve/other.py") == []


def test_host_sync_sanctioned_sampled_sync(tmp_path):
    """stepstats.sampled_sync is THE blessed sync seam on the serve
    hot path: never flagged, while every other block_until_ready
    spelling (method form AND jax.block_until_ready call form) is."""
    _write(tmp_path, "serve/decode_engine.py", """\
        import jax
        from skypilot_tpu.observability import stepstats

        @jax.jit
        def _engine_step(tokens, cache):
            return tokens + 1, cache

        def engine_loop(tokens, cache):
            while True:
                tokens, cache = _engine_step(tokens, cache)
                if stepstats.ENABLED and stepstats.sync_due():
                    device_s = stepstats.sampled_sync(tokens)
                jax.block_until_ready(tokens)
                tokens.block_until_ready()
        """)
    findings = _run(tmp_path, "stpu-host-sync")
    lines = _lines(findings, "serve/decode_engine.py")
    # The sanctioned helper (line 12) passes; both raw sync spellings
    # (13: call form, 14: method form) are findings.
    assert 12 not in lines
    assert 13 in lines and 14 in lines
    by_line = {f.line: f.message for f in findings}
    assert "sampled_sync" in by_line[13]


def test_host_sync_noqa(tmp_path):
    _write(tmp_path, "serve/gang_replica.py", """\
        def broadcast_generate(arr):
            arr.block_until_ready()  # noqa: stpu-host-sync gang barrier needs a hard sync point
            return arr.item()
        """)
    findings = _run(tmp_path, "stpu-host-sync")
    assert _lines(findings, "serve/gang_replica.py") == [3]


def test_host_sync_train_loop_bad_fixture(tmp_path):
    """The rule now targets the train loops: a recipe loop that
    float()s its loss every step, .item()s a metric, or hard-syncs
    with block_until_ready is flagged like the decode engine."""
    _write(tmp_path, "recipes/llama_lora.py", """\
        import jax

        @jax.jit
        def step_fn(state, batch):
            return state, batch.sum()

        def run(state, batches):
            for batch in batches:
                state, loss = step_fn(state, batch)
                log = float(loss)
                item = loss.item()
                loss.block_until_ready()
        """)
    findings = _run(tmp_path, "stpu-host-sync")
    assert _lines(findings, "recipes/llama_lora.py") == [10, 11, 12]


def test_host_sync_train_loop_good_fixture(tmp_path):
    """The sanctioned train-loop pattern passes clean: DelayedFetch
    rotation + the literal jax.device_get of the PREVIOUS handle, and
    trainstats.sampled_sync as the only in-loop device sync."""
    _write(tmp_path, "recipes/llama_lora.py", """\
        import jax
        from skypilot_tpu.observability import trainstats
        from skypilot_tpu.train import trainer

        @jax.jit
        def step_fn(state, batch):
            return state, batch.sum()

        def run(state, batches):
            delayed = trainer.DelayedFetch()
            for batch in batches:
                state, loss = step_fn(state, batch)
                prev = delayed.rotate(loss)
                if prev is not None:
                    host_loss = jax.device_get(prev)
                    fetched = float(host_loss)
                if trainstats.ENABLED and trainstats.sync_due():
                    device_s = trainstats.sampled_sync(loss)
        """)
    findings = _run(tmp_path, "stpu-host-sync")
    assert _lines(findings, "recipes/llama_lora.py") == []


def test_host_sync_jit_factory_taints_train_loop(tmp_path):
    """`step = trainer.make_train_step(...)` is a jitted entry point
    (_JIT_FACTORIES) even with no local @jax.jit — the loop calling it
    is hot and a per-step float(metrics) there is a finding."""
    _write(tmp_path, "recipes/mixtral_ep.py", """\
        from skypilot_tpu.train import trainer

        def run(state, batches, tx, mesh, rules):
            step = trainer.make_train_step(lambda p, t, c: t, tx,
                                           mesh, rules)
            for batch in batches:
                state, metrics = step(state, batch)
                loss = float(metrics["loss"])
        """)
    findings = _run(tmp_path, "stpu-host-sync")
    assert _lines(findings, "recipes/mixtral_ep.py") == [8]
    # The same loop in a NON-target file stays out of scope.
    _write(tmp_path, "recipes/other_recipe.py", """\
        from skypilot_tpu.train import trainer

        def run(state, batches, tx, mesh, rules):
            step = trainer.make_train_step(lambda p, t, c: t, tx,
                                           mesh, rules)
            for batch in batches:
                state, metrics = step(state, batch)
                loss = float(metrics["loss"])
        """)
    findings = _run(tmp_path, "stpu-host-sync")
    assert _lines(findings, "recipes/other_recipe.py") == []


def test_armed_guard_rule(tmp_path):
    """The good/bad/noqa trio for stpu-armed-guard: unguarded
    observability calls on a hot module are findings; flag guards
    (plain, compound, alias, elif, in-test), armed-only helpers, the
    sanctioned no-op callees, and explained noqas all pass."""
    _write(tmp_path, "serve/decode_engine.py", """\
        from skypilot_tpu.observability import reqlog, stepstats, tracing
        from skypilot_tpu.utils import fault_injection

        def bad_step(live):
            stepstats.record(live=len(live))
            fault_injection.fire("engine.step")

        def good_plain(live):
            if stepstats.ENABLED:
                stepstats.record(live=len(live))

        def good_compound(stats):
            if reqlog.ENABLED and stats.get("reqlog") is not None:
                reqlog.write_record(stats["reqlog"])

        def good_alias(live):
            armed = stepstats.ENABLED
            if armed and live:
                stepstats.record(live=len(live))

        def good_in_test():
            if stepstats.ENABLED and stepstats.sync_due():
                pass

        def good_elif(x):
            if x:
                pass
            elif reqlog.ENABLED and x is None:
                reqlog.mint_id()

        def _record_helper(i):
            stepstats.record_admission(i)

        def caller(i):
            if stepstats.ENABLED:
                _record_helper(i)

        def good_sanctioned(headers):
            return tracing.extract(headers)

        def noqad():
            stepstats.record(x=1)  # noqa: stpu-armed-guard one-shot startup probe, never per-token

        def bad_disarmed_branch():
            if stepstats.ENABLED:
                pass
            else:
                stepstats.record(x=1)
        """)
    findings = _run(tmp_path, "stpu-armed-guard")
    lines = _lines(findings, "serve/decode_engine.py")
    assert lines == [5, 6, 48]
    assert "stepstats.ENABLED" in {f.line: f.message
                                   for f in findings}[5]


def test_armed_guard_unguarded_helper_is_flagged(tmp_path):
    """A helper whose call sites do NOT all guard gets no armed-only
    credit — the call inside it is a finding."""
    _write(tmp_path, "serve/load_balancer.py", """\
        from skypilot_tpu.observability import reqlog

        def helper(rec):
            reqlog.write_record(rec)

        def guarded_caller(rec):
            if reqlog.ENABLED:
                helper(rec)

        def unguarded_caller(rec):
            helper(rec)
        """)
    findings = _run(tmp_path, "stpu-armed-guard")
    assert _lines(findings, "serve/load_balancer.py") == [4]


def test_armed_guard_targets_hot_modules_only(tmp_path):
    """Cold control-plane code is out of scope: the same unguarded
    call in a non-target file is never flagged."""
    _write(tmp_path, "serve/controller.py", """\
        from skypilot_tpu.observability import stepstats

        def f():
            stepstats.record(x=1)
        """)
    findings = _run(tmp_path, "stpu-armed-guard")
    assert _lines(findings, "serve/controller.py") == []


def test_env_rule_seeded_fixture(tmp_path):
    """Acceptance: an unregistered STPU_* read fails; a default
    literal that disagrees with env_contract.py fails; registered
    reads with the registered default pass."""
    _write(tmp_path, "env_probe.py", """\
        import os
        A = os.environ.get("STPU_NOT_A_REAL_KNOB", "1")
        B = os.environ.get("STPU_ENGINE_SLOTS", "8")
        C = os.environ["STPU_ALSO_NOT_REAL"]
        D = os.environ.get("STPU_ENGINE_SLOTS", "4")
        E = os.environ.get("STPU_LB_POLICY")
        F = os.getenv("STPU_THIRD_FAKE")
        G = os.environ.get("HOME", "/root")
        H = os.environ.get("STPU_GRANDFATHERED", "x")  # noqa: stpu-env migration shim removed next release
        I = os.environ.get("STPU_DISABLE_EVENTS")
        """)
    findings = _run(tmp_path, "stpu-env")
    lines = _lines(findings, "env_probe.py")
    # Line 10: a presence-style read (no inline default) of a
    # defaulted knob is NOT a disagreement — only inline literals are.
    assert lines == [2, 3, 4, 7]
    by_line = {f.line: f.message for f in findings}
    assert "not registered" in by_line[2]
    assert "registers '4'" in by_line[3]


def test_env_rule_resolves_constants(tmp_path):
    """Reads through module constants resolve: locally
    (`ENABLE_ENV = "STPU_TRACE"`), and cross-file for dotted reads
    (`tracing.ENV_CTX`). Ambiguous bare names never resolve."""
    _write(tmp_path, "tracing.py", """\
        import os
        ENABLE_ENV = "STPU_TRACE"
        FAKE_ENV = "STPU_CONSTANT_FAKE"
        armed = os.environ.get(ENABLE_ENV, "0") == "1"
        bad = os.environ.get(FAKE_ENV)
        """)
    _write(tmp_path, "consumer.py", """\
        import os
        from . import tracing
        ctx = os.environ.get(tracing.FAKE_ENV)
        """)
    findings = _run(tmp_path, "stpu-env")
    assert _lines(findings, "tracing.py") == [5]
    assert _lines(findings, "consumer.py") == [3]


def test_env_registry_covers_repo_reads():
    """Every STPU_* env read in skypilot_tpu/ resolves through
    env_contract.py (the repo-wide clean run enforces it; this pins
    the rule actually VISITED the tree by checking a known knob)."""
    findings = analysis.run_check(rules=["stpu-env"])
    assert findings == [], "\n".join(f.render() for f in findings)
    assert "STPU_ENGINE_SLOTS" in env_contract.REGISTRY
    assert env_contract.REGISTRY["STPU_HOME"].default == "~/.stpu"


def test_unparsable_file_is_a_finding(tmp_path):
    """A file that fails ast.parse must FAIL the gate (stpu-parse), not
    silently pass every AST rule."""
    _write(tmp_path, "train/checkpoint.py", """\
        def write_state(p):
        <<<<<<< merge conflict
            open(p, "w").write("x")
        """)
    findings = analysis.run_check(paths=[tmp_path])
    parse = [f for f in findings if f.rule == "stpu-parse"]
    assert len(parse) == 1 and parse[0].path == "train/checkpoint.py"
    assert "syntax error" in parse[0].message


def test_targets_are_path_bounded(tmp_path):
    """Suffix matching is '/'-bounded: restrain/checkpoint.py is not
    train/checkpoint.py, observe/decode_engine.py is not the engine."""
    body = 'f = open("x", "w")\n'
    _write(tmp_path, "restrain/checkpoint.py", body)
    _write(tmp_path, "train/checkpoint.py", body)
    findings = _run(tmp_path, "stpu-atomic")
    assert _lines(findings, "train/checkpoint.py") == [1]
    assert _lines(findings, "restrain/checkpoint.py") == []
    _write(tmp_path, "observe/decode_engine.py",
           "def f(a):\n    return a.item()\n")
    findings = _run(tmp_path, "stpu-host-sync")
    assert _lines(findings, "observe/decode_engine.py") == []


def test_atomic_shim_lints_explicit_paths(tmp_path):
    """Historical API: tools/check_atomic_writes.check([paths]) lints
    exactly the files it is given, whatever they are named."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_atomic_writes
        bad = _write(tmp_path, "some_state_writer.py",
                     'f = open("x", "w")\n')
        violations = check_atomic_writes.check([bad])
        assert len(violations) == 1 and "stpu-atomic" in violations[0]
    finally:
        sys.path.pop(0)


# ================================================= CLI + json schema
def test_cli_check_clean_and_json(tmp_path):
    from skypilot_tpu import cli
    runner = CliRunner()
    bad = _write(tmp_path, "bad.py",
                 "import time\nd = time.time() - t0\n")
    result = runner.invoke(cli.cli, ["check", str(bad)])
    assert result.exit_code == 1
    assert "bad.py:2:stpu-wallclock:" in result.output

    result = runner.invoke(cli.cli, ["check", "--json", str(bad)])
    assert result.exit_code == 1
    payload = json.loads(result.output)
    assert isinstance(payload, list) and payload
    # Pinned schema: exactly these keys.
    assert set(payload[0]) == {"path", "line", "rule", "message"}
    assert payload[0]["rule"] == "stpu-wallclock"
    assert payload[0]["line"] == 2

    good = _write(tmp_path, "good.py", "x = 1\n")
    result = runner.invoke(cli.cli, ["check", str(good)])
    assert result.exit_code == 0, result.output
    result = runner.invoke(cli.cli, ["check", "--json", str(good)])
    assert json.loads(result.output) == []


def test_cli_check_rule_selection(tmp_path):
    from skypilot_tpu import cli
    runner = CliRunner()
    bad = _write(tmp_path, "bad.py",
                 "import time\nd = time.time() - t0\n")
    result = runner.invoke(
        cli.cli, ["check", "--rule", "stpu-donation", str(bad)])
    assert result.exit_code == 0, result.output
    result = runner.invoke(
        cli.cli, ["check", "--rule", "stpu-nonsense", str(bad)])
    assert result.exit_code != 0
    assert "unknown rule" in result.output

    result = runner.invoke(cli.cli, ["check", "--list-rules"])
    assert result.exit_code == 0
    assert "stpu-donation" in result.output
    assert "stpu-env" in result.output


def test_cli_check_repo_default_clean():
    """`stpu check` with no PATHS scans skypilot_tpu/ and exits 0."""
    from skypilot_tpu import cli
    runner = CliRunner()
    result = runner.invoke(cli.cli, ["check"])
    assert result.exit_code == 0, result.output
    assert "0 finding(s)" in result.output


# ================================================= tools/ shims
def test_tools_shims_still_work():
    """`python tools/check_*.py` invocations keep working (exit 0 on
    the clean repo, framework-rendered output)."""
    for script in ("check_clocks.py", "check_excepts.py",
                   "check_collectives.py", "check_atomic_writes.py"):
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / script)],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, (script, proc.stdout, proc.stderr)
        assert "OK" in proc.stdout


# ================================================= env-table doc sync
def test_env_table_doc_in_sync():
    """docs/static-analysis.md embeds `stpu check --env-table` output
    between markers; it must be byte-identical to the registry render
    so the doc can never drift from code."""
    doc = (REPO / "docs" / "static-analysis.md").read_text()
    begin = "<!-- env-table:begin (stpu check --env-table) -->"
    end = "<!-- env-table:end -->"
    assert begin in doc and end in doc
    embedded = doc.split(begin, 1)[1].split(end, 1)[0].strip()
    assert embedded == env_contract.render_markdown_table(), (
        "docs/static-analysis.md env table is stale — regenerate with "
        "`stpu check --env-table`")


def test_deleted_serving_switch_is_in_no_registry_and_no_source():
    """STPU_KV_PAGED selected the row-cache engine, which is gone: the
    variable is registered nowhere, the generated table (held equal to
    the registry above) does not carry it, and nothing under
    skypilot_tpu/ reads or documents it."""
    assert "STPU_KV_PAGED" not in env_contract.REGISTRY
    with pytest.raises(KeyError):
        env_contract.get("STPU_KV_PAGED")
    assert "STPU_KV_PAGED" not in env_contract.render_markdown_table()
    doc = (REPO / "docs" / "static-analysis.md").read_text()
    assert "KV_PAGED" not in doc
    for path in (REPO / "skypilot_tpu").rglob("*.py"):
        assert "KV_PAGED" not in path.read_text(), path


# ============================================ serving-stack layering
def _imported_modules(path):
    """Every ``skypilot_tpu.*`` module a file imports, at module level
    or inside a function (read with ``ast``; ``from a.b import c`` is
    counted as both ``a.b`` and ``a.b.c``)."""
    import ast
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:      # relative: resolve against the package
                pkg = path.relative_to(REPO).with_suffix("").parts
                base = ".".join(pkg[:len(pkg) - node.level]
                                + ((base,) if base else ()))
            found.add(base)
            found.update(f"{base}.{a.name}" for a in node.names)
    return {m for m in found if m.startswith("skypilot_tpu")}


@pytest.mark.parametrize("sources,forbidden", [
    ("models", ("serve", "recipes", "benchmark", "tune")),
    ("serve/kv_pool.py", ("serve.decode_engine",)),
    ("serve/decode_engine.py", ("recipes",)),
    ("serve/decode_engine.py", ("benchmark",)),
], ids=["models", "kv_pool", "engine-recipes", "engine-benchmark"])
def test_serving_stack_imports_point_downwards(sources, forbidden):
    """A request goes handler -> DecodeEngine -> kv_pool -> models;
    imports go the same way and never back up (function-level imports
    included). decode_engine <-> tune stays a cycle, a named debt
    (ROADMAP Design 7)."""
    root = REPO / "skypilot_tpu" / sources
    files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
    assert files
    for path in files:
        for module in _imported_modules(path):
            for layer in forbidden:
                banned = f"skypilot_tpu.{layer}"
                assert module != banned and \
                    not module.startswith(banned + "."), \
                    f"{path.relative_to(REPO)} imports {module}"


def test_cli_env_table_matches_registry():
    from skypilot_tpu import cli
    runner = CliRunner()
    result = runner.invoke(cli.cli, ["check", "--env-table"])
    assert result.exit_code == 0
    assert result.output.strip() == env_contract.render_markdown_table()
    # Every registered knob appears exactly once.
    for name in env_contract.REGISTRY:
        assert f"`{name}`" in result.output


def test_registry_rejects_bad_knobs():
    with pytest.raises(ValueError):
        env_contract._k("NOT_STPU", None, "doc")
    with pytest.raises(ValueError):
        env_contract._k("STPU_X", None, "   ")
