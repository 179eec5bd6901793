"""The engine autotuner (skypilot_tpu/tune/): manifest contract,
geometry resolution, parity at non-default constants, handshake drift.

Four layers, cheapest first:

* the manifest SCHEMA is pinned (constants + validate() rejections) so
  the document shape can't drift silently under an unchanged version;
* load/save round-trip, fail-closed fallback on corrupt/stale/
  sha-mismatched files, and the env-var resolution order;
* resolve_kv_geometry's 0-sentinel override policy (manifest fills
  only knobs the caller left unset; explicit args win; the payload-sha
  tag rides the geometry dict, so gang followers with a drifted
  manifest die at join);
* engine-output parity AT tuned constants — the same
  tune.parity.check_parity gate `stpu tune` runs on every winner
  before persisting, here parametrized over families, with the window
  set by a tuned ``block`` and by ``window_blocks``, at a deliberately
  non-default tile/chunk.
"""
import json
import socket
import threading

import jax
import pytest

from skypilot_tpu.serve import decode_engine, gang_replica
from skypilot_tpu.serve.decode_engine import DecodeEngine
from skypilot_tpu.tune import manifest as tune_manifest
from skypilot_tpu.tune import sweep as tune_sweep
from skypilot_tpu.tune.parity import check_parity


PROV = {"device_kind": "cpu", "commit": "abc1234",
        "created": "2026-08-06T00:00:00+0000"}


@pytest.fixture
def manifest_env(tmp_state_dir, monkeypatch):
    """Hermetic manifest state: ~/.stpu in a tmpdir, no ambient
    STPU_TUNE_MANIFEST, caches cleared both sides."""
    monkeypatch.delenv("STPU_TUNE_MANIFEST", raising=False)
    tune_manifest.reset_for_tests()
    yield tmp_state_dir
    tune_manifest.reset_for_tests()


def _tiny():
    from skypilot_tpu.models import llama
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    return llama, cfg, llama.init(cfg, jax.random.key(0))


# ==================================================== schema contract
def test_manifest_schema_pinned():
    """The constants the doc shape hangs off: bumping any of these is
    a schema revision and must be a conscious change."""
    assert tune_manifest.SCHEMA_VERSION == 1
    assert tune_manifest.ENTRY_KNOBS == ("block", "chunk",
                                         "window_blocks", "spec_k")
    assert tune_manifest.REQUIRED_PROVENANCE == ("device_kind",
                                                 "commit", "created")
    assert tune_manifest.ENV_MANIFEST == "STPU_TUNE_MANIFEST"


def test_tuning_key_bands_and_quant_modes():
    assert tune_manifest.tuning_key("llama", 2) == "llama|b1-4|tp1|bf16"
    assert tune_manifest.tuning_key(
        "mixtral", 8, tp=4, kv_quant=True,
        weight_quant=True) == "mixtral|b5-16|tp4|q8kvw"
    assert tune_manifest.batch_band(17) == "b17+"
    assert tune_manifest.quant_mode(True, False) == "q8kv"
    assert tune_manifest.quant_mode(False, True) == "q8w"


def _valid_doc(entries=None):
    payload = {"provenance": dict(PROV),
               "entries": entries if entries is not None else {
                   "llama|b1-4|tp1|bf16": {"block": 128,
                                           "parity": "pass"}}}
    return {"schema": tune_manifest.SCHEMA_VERSION,
            "sha256": tune_manifest.payload_sha(payload),
            "payload": payload}


def test_validate_accepts_and_rejects():
    tune_manifest.validate(_valid_doc())

    with pytest.raises(tune_manifest.ManifestError, match="stale"):
        doc = _valid_doc()
        doc["schema"] = 99
        tune_manifest.validate(doc)

    with pytest.raises(tune_manifest.ManifestError, match="sha256"):
        doc = _valid_doc()
        doc["payload"]["entries"]["llama|b1-4|tp1|bf16"]["block"] = 256
        tune_manifest.validate(doc)          # payload edited, sha not

    with pytest.raises(tune_manifest.ManifestError, match="tuning key"):
        tune_manifest.validate(_valid_doc(
            {"llama|bf16": {"block": 128, "parity": "pass"}}))

    with pytest.raises(tune_manifest.ManifestError, match="no tuned"):
        tune_manifest.validate(_valid_doc(
            {"llama|b1-4|tp1|bf16": {"parity": "pass"}}))

    with pytest.raises(tune_manifest.ManifestError, match="int"):
        tune_manifest.validate(_valid_doc(
            {"llama|b1-4|tp1|bf16": {"block": True, "parity": "pass"}}))

    with pytest.raises(tune_manifest.ManifestError,
                       match="out of range"):
        tune_manifest.validate(_valid_doc(
            {"llama|b1-4|tp1|bf16": {"chunk": 0, "parity": "pass"}}))

    # spec_k = 0 is a legal tuned value (drafting off) ...
    tune_manifest.validate(_valid_doc(
        {"llama|b1-4|tp1|bf16": {"spec_k": 0, "parity": "pass"}}))

    with pytest.raises(tune_manifest.ManifestError, match="parity"):
        tune_manifest.validate(_valid_doc(
            {"llama|b1-4|tp1|bf16": {"block": 128}}))

    with pytest.raises(tune_manifest.ManifestError,
                       match="provenance"):
        doc = _valid_doc()
        del doc["payload"]["provenance"]["commit"]
        doc["sha256"] = tune_manifest.payload_sha(doc["payload"])
        tune_manifest.validate(doc)


# ================================================= round-trip + fallback
def test_save_load_entry_for_round_trip(manifest_env):
    entries = {"llama|b1-4|tp1|bf16":
               {"block": 128, "chunk": 32, "parity": "pass"}}
    doc = tune_manifest.save(entries, PROV)
    assert tune_manifest.default_path().is_file()

    payload, tag = tune_manifest.load(tune_manifest.default_path())
    assert payload["entries"] == entries
    assert tag == doc["sha256"][:12]

    # Unset env + file at the default path -> auto-pickup.
    entry, got_tag = tune_manifest.entry_for(family="llama", slots=2)
    assert entry == entries["llama|b1-4|tp1|bf16"]
    assert got_tag == tag
    # A config with no entry: default, same (valid) manifest.
    assert tune_manifest.entry_for(family="gemma", slots=2) == \
        (None, "default")


def test_save_merges_existing_entries(manifest_env):
    tune_manifest.save({"llama|b1-4|tp1|bf16":
                        {"block": 128, "parity": "pass"}}, PROV)
    tune_manifest.save({"gemma|b1-4|tp1|bf16":
                        {"chunk": 32, "parity": "pass"}}, PROV)
    payload, _ = tune_manifest.load(tune_manifest.default_path())
    assert set(payload["entries"]) == {"llama|b1-4|tp1|bf16",
                                       "gemma|b1-4|tp1|bf16"}
    # merge=False replaces.
    tune_manifest.save({"mixtral|b1-4|tp1|bf16":
                        {"spec_k": 2, "parity": "pass"}}, PROV,
                       merge=False)
    payload, _ = tune_manifest.load(tune_manifest.default_path())
    assert set(payload["entries"]) == {"mixtral|b1-4|tp1|bf16"}


def test_resolve_path_env_contract(manifest_env, monkeypatch):
    # Unset + no file -> None (defaults).
    assert tune_manifest.resolve_path() is None
    # "0" disables even when the default file exists.
    tune_manifest.save({"llama|b1-4|tp1|bf16":
                        {"block": 128, "parity": "pass"}}, PROV)
    assert tune_manifest.resolve_path() == tune_manifest.default_path()
    monkeypatch.setenv("STPU_TUNE_MANIFEST", "0")
    assert tune_manifest.resolve_path() is None
    assert tune_manifest.entry_for(family="llama", slots=2) == \
        (None, "default")
    # An explicit path wins over the default location.
    other = manifest_env.parent / "other.json"
    tune_manifest.save({"llama|b1-4|tp1|bf16":
                        {"block": 512, "parity": "pass"}}, PROV,
                       path=other, merge=False)
    monkeypatch.setenv("STPU_TUNE_MANIFEST", str(other))
    entry, _ = tune_manifest.entry_for(family="llama", slots=2)
    assert entry["block"] == 512


@pytest.mark.parametrize("corruption", ["garbage", "sha", "stale"])
def test_corrupt_or_stale_manifest_falls_back(manifest_env, capsys,
                                              corruption):
    """A bad manifest must never keep an engine from serving: one
    stderr warning, then default constants."""
    path = tune_manifest.default_path()
    doc = tune_manifest.save({"llama|b1-4|tp1|bf16":
                              {"block": 128, "chunk": 32,
                               "parity": "pass"}}, PROV)
    if corruption == "garbage":
        path.write_text("{not json")
    elif corruption == "sha":
        doc["payload"]["entries"]["llama|b1-4|tp1|bf16"]["block"] = 16
        path.write_text(json.dumps(doc))     # sha now wrong
    else:
        doc["schema"] = 0                    # stale version
        path.write_text(json.dumps(doc))
    tune_manifest.reset_for_tests()

    assert tune_manifest.entry_for(family="llama", slots=2) == \
        (None, "default")
    assert "ignoring manifest" in capsys.readouterr().err
    # Warn once per path, not per lookup.
    tune_manifest.entry_for(family="llama", slots=2)
    assert capsys.readouterr().err == ""

    # The engine still resolves (default constants) and serves.
    geo = decode_engine.resolve_kv_geometry(slots=2, max_seq=64,
                                            family="llama")
    assert geo["manifest"] == "default"
    assert geo["block"] == 64                # SPLIT_KV_BLOCK clamped


# ====================================== geometry resolution + override
def test_manifest_fills_only_unset_knobs(manifest_env):
    tune_manifest.save(
        {"llama|b1-4|tp1|bf16": {"block": 32, "chunk": 16,
                                 "window_blocks": 2, "spec_k": 2,
                                 "parity": "pass"}}, PROV)
    tag = tune_manifest.entry_for(family="llama", slots=2)[1]

    geo = decode_engine.resolve_kv_geometry(slots=2, max_seq=64,
                                            family="llama")
    assert (geo["block"], geo["chunk"], geo["window"],
            geo["spec_k"]) == (32, 16, 32, 2)
    assert geo["manifest"] == tag

    # Explicit knobs win over the manifest; untouched ones still fill.
    geo = decode_engine.resolve_kv_geometry(
        slots=2, max_seq=64, prefill_chunk=8,
        family="llama")
    assert geo["chunk"] == 8
    assert geo["block"] == 32
    # kv_block_tokens is the pool's name for chunk — also explicit.
    geo = decode_engine.resolve_kv_geometry(
        slots=2, max_seq=64, kv_block_tokens=8,
        family="llama")
    assert geo["chunk"] == 8

    # use_manifest=False (bench legs, parity reference engines).
    geo = decode_engine.resolve_kv_geometry(slots=2, max_seq=64,
                                            family="llama",
                                            use_manifest=False)
    assert geo["manifest"] == "default"
    assert geo["block"] == 64 and geo["chunk"] == 64

    # No family (legacy callers): no lookup at all.
    geo = decode_engine.resolve_kv_geometry(slots=2, max_seq=64)
    assert geo["manifest"] == "default"


def test_engine_startup_loads_manifest_constants(manifest_env):
    """DecodeEngine resolves the manifest at construction: tuned
    constants land in kv_config() (what /perf surfaces and the gang
    handshake compares) without any per-call plumbing."""
    tune_manifest.save(
        {"llama|b1-4|tp1|bf16": {"block": 32, "chunk": 16,
                                 "parity": "pass"}}, PROV)
    mdl, cfg, params = _tiny()
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64)
    kv = eng.kv_config()
    assert kv["block"] == 32 and kv["chunk"] == 16
    assert kv["manifest"] != "default"
    # Same knobs, manifest off: the handshake dicts must differ.
    ref = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       use_manifest=False)
    assert ref.kv_config() != kv


def test_follower_with_drifted_manifest_dies_at_join(manifest_env):
    """Tuned geometry rides the gang welcome: a follower that resolved
    a different (or no) manifest must die at join (rc 1), not decode
    with drifted tiles out of lockstep."""
    tune_manifest.save(
        {"llama|b1-4|tp1|bf16": {"block": 32, "chunk": 16,
                                 "parity": "pass"}}, PROV)
    topo = gang_replica.ReplicaTopology(hosts=2)
    leader_kv = decode_engine.resolve_kv_geometry(
        slots=2, max_seq=64, family="llama")
    assert leader_kv["manifest"] != "default"
    leader = gang_replica.GangLeader(topo, port=0, kv_config=leader_kv)
    try:
        sock = socket.create_connection(("127.0.0.1", leader.port),
                                        timeout=5.0)
        wf, rf = sock.makefile("wb"), sock.makefile("rb")
        gang_replica._send_line(wf, {"op": "hello", "rank": 1,
                                     "pid": 1})
        assert json.loads(rf.readline())["kv"] == leader_kv
        sock.close()

        class _StubEngine:
            def start(self):
                return self

            def shutdown(self):
                pass

        rc_box = []

        def follower():
            rc_box.append(gang_replica.follower_serve(
                _StubEngine, topo, f"127.0.0.1:{leader.port}", rank=1,
                kv_config=decode_engine.resolve_kv_geometry(
                    slots=2, max_seq=64, family="llama",
                    use_manifest=False)))

        t = threading.Thread(target=follower, daemon=True)
        t.start()
        t.join(timeout=30.0)
        assert rc_box == [1]
    finally:
        leader.shutdown()


# ===================================================== sweep mechanics
def test_candidate_grids_include_defaults():
    for mode in tune_sweep.MODES:
        cands = tune_sweep._candidates(mode)
        assert tune_sweep.DEFAULTS[mode] in cands
        assert len(cands) == len({tuple(sorted(c.items()))
                                  for c in cands})  # no dupes
        axes = tune_sweep.SEARCH_SPACE[mode]
        for cand in cands:
            assert set(cand) == set(axes)


def test_tune_cli_registered():
    from click.testing import CliRunner

    from skypilot_tpu import cli
    result = CliRunner().invoke(cli.cli, ["tune", "--help"])
    assert result.exit_code == 0
    assert "manifest" in result.output


# ============================================ parity at tuned constants
# The same gate `stpu tune` runs per winner, at a deliberately
# non-default geometry (tile 32, chunk 16 — tile boundaries inside
# every prompt). Each case drives greedy AND seeded requests; greedy
# output is additionally checked against the models.decode fixed path.
# llama runs in tier-1 (the shared engine machinery); mixtral/gemma
# recompile the same programs against their own attention variants and
# ride the slow lane with the other long-compile suites.
_FAMILIES = ["llama",
             pytest.param("mixtral", marks=pytest.mark.slow),
             pytest.param("gemma", marks=pytest.mark.slow)]


@pytest.mark.parametrize("family", _FAMILIES)
def test_parity_at_tuned_constants_block(family):
    """A manifest's ``block`` sets the attention window where no
    ``window_blocks`` does (two 16-token blocks a tile)."""
    check_parity(family, block=32, chunk=16,
                 n_requests=2, max_tokens=4)


@pytest.mark.parametrize("family", _FAMILIES)
def test_parity_at_tuned_constants_paged(family):
    check_parity(family, chunk=16, window_blocks=2,
                 n_requests=2, max_tokens=4)


def test_parity_gate_catches_a_planted_divergence(monkeypatch):
    """The gate itself must be falsifiable: feed it a reference that
    cannot match and the ParityError must fire (a gate that never
    fails gates nothing)."""
    from skypilot_tpu.tune import parity as parity_mod

    real = parity_mod._drain
    flip = {"n": 0}

    def crooked(engine, specs):
        out = real(engine, specs)
        flip["n"] += 1
        if flip["n"] == 2:                   # corrupt the reference run
            out = [list(s) for s in out]
            out[0][0] = (out[0][0] + 1) % 100
        return out

    monkeypatch.setattr(parity_mod, "_drain", crooked)
    with pytest.raises(parity_mod.ParityError):
        parity_mod.check_parity("llama", block=32, n_requests=1,
                                max_tokens=3)
