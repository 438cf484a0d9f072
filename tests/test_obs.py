"""Structured telemetry layer (repro/obs): event registry semantics,
the timed span trees of both engines, spans on the profiler's trace and
their compile counts, client-health counters, byte-ledger
reconciliation against the trainer's accounting, the no-op-sink
bit-parity contract, and the JSONL -> report pipeline."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import async_rounds
from repro.core.federated import FederatedTrainer
from repro.obs import report as obs_report
from repro.obs import telemetry as obslib


class _ToyAdapter:
    """Tiny real-training adapter (mirrors tests/test_async.py): params
    drift toward each client's data mean, so rounds are cheap to compile
    and a NaN shard produces a NaN-trained device."""

    def init(self, key):
        return {"a": jnp.zeros((4,), jnp.float32),
                "b": jnp.zeros((4,), jnp.float32)}

    def subnet_mask(self, params):
        return {"a": jnp.asarray(True), "b": jnp.asarray(False)}

    @staticmethod
    def _loss(params, batch):
        x = batch["x"]
        err_a = params["a"][None] - x
        err_b = params["b"][None] - 2.0 * x
        return jnp.mean(err_a ** 2) + jnp.mean(err_b ** 2)

    loss_simple = loss_complex = loss_side = _loss

    def evaluate(self, params, batch):
        return {"acc_simple": jnp.mean(params["a"]),
                "acc_complex": jnp.mean(params["b"])}


def _shards(n_devices, seed=0, poison=None):
    rng = np.random.default_rng(seed)
    shards = [{"x": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))}
              for _ in range(n_devices)]
    if poison is not None:
        shards[poison]["x"] = shards[poison]["x"].at[0, 0].set(jnp.nan)
    return shards


def _make_trainer(telemetry=None, *, chunk=2, poison=None, **fed_kw):
    fed = FedConfig(n_devices=8, n_simple=4, participation=1.0,
                    local_epochs=1, lr=0.1, batch_size=4,
                    algorithm="fedhen", seed=0, cohort_chunk=chunk,
                    **fed_kw)
    return FederatedTrainer(_ToyAdapter(), fed, _shards(8, poison=poison),
                            telemetry=telemetry)


def _max_abs_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# Registry semantics (no jax involved)
# ---------------------------------------------------------------------------

def test_span_paths_nest():
    mem = obslib.MemorySink()
    tel = obslib.Telemetry([mem])
    with tel.span("outer"):
        with tel.span("inner", tag=3):
            tel.counter("c", 1)
        with tel.span("sibling"):
            pass
    spans = mem.of_kind("span")
    # spans emit on exit: inner closes first, then its sibling, then outer
    assert [e["path"] for e in spans] == \
        ["outer/inner", "outer/sibling", "outer"]
    # every span is timed, and the outer one holds both inner ones
    assert all(e["dur_s"] >= 0 for e in spans)
    outer = mem.named("outer")[0]
    assert outer["dur_s"] >= sum(e["dur_s"] for e in spans[:2])
    inner = mem.named("inner")[0]
    assert inner["tag"] == 3
    # jax is loaded here, so each span counts the compiles inside it
    assert [e["compiles"] for e in spans] == [0, 0, 0]
    assert mem.named("c")[0]["value"] == 1
    # seq is emission order
    assert [e["seq"] for e in mem.events] == list(range(len(mem.events)))


def test_disabled_telemetry_emits_nothing():
    mem = obslib.MemorySink()
    tel = obslib.Telemetry([mem], enabled=False)
    with tel.span("x"):
        tel.counter("c", 1)
        tel.ledger("l", {"a": 1})
        tel.log("hi")
        with tel.span("y"):
            pass
    assert mem.events == []
    assert not obslib.NOOP.enabled  # the module singleton stays disabled


def test_jsonable_coerces_array_scalars():
    assert obslib.jsonable(jnp.float32(1.5)) == 1.5
    assert obslib.jsonable(np.int64(7)) == 7
    assert obslib.jsonable({"k": (np.float32(2.0),)}) == {"k": [2.0]}
    json.dumps(obslib.jsonable({"a": jnp.zeros(())}))  # must not raise


# ---------------------------------------------------------------------------
# Sync engine: span tree, counters, byte ledger
# ---------------------------------------------------------------------------

def test_sync_two_round_span_tree_and_ledgers():
    mem = obslib.MemorySink()
    tr = _make_trainer(obslib.Telemetry([mem]))
    tr.run_round()
    tr.run_round()

    # the host's timed phases; the round jit's stages are device tags
    want_phases = ["round/sample_gather", "round/execute", "round"]
    for r in (0, 1):
        spans = [e for e in mem.of_kind("span")
                 if e["round"] == r and e["name"] not in
                 ("trace_lower", "compile")]
        assert [e["path"] for e in spans] == want_phases, (r, spans)
        assert all(e["dur_s"] > 0 for e in spans)
        rnd, execute = spans[-1], spans[1]
        assert rnd["dur_s"] >= execute["dur_s"]
    # the compile split happens exactly once, on the first round, and
    # only the first round compiles
    assert [e["round"] for e in mem.named("trace_lower")] == [0]
    assert [e["round"] for e in mem.named("compile")] == [0]
    assert mem.named("compile")[0]["compiles"] >= 1
    assert [e["compiles"] > 0 for e in mem.named("round")] == [True, False]
    # and the roofline ledger rides the compiled first round (the toy
    # adapter has no matmuls, so assert on memory traffic, not flops)
    roof = mem.named("roofline")
    assert len(roof) == 1 and roof[0]["values"]["hbm_bytes"] > 0

    # a synchronous round has no staleness to report
    assert mem.named("staleness_hist") == []

    # client health: clean run, no exclusions, chunk 2 divides k=4
    assert [e["value"] for e in mem.named("nan_excluded_devices")] == [0, 0]
    assert [e["value"] for e in mem.named("padding_weight0_clients")] == \
        [0, 0]

    # byte ledger: EXACT equality with the trainer's measured accounting
    ledgers = [e["values"] for e in mem.named("comm_bytes")]
    assert len(ledgers) == 2
    for i, led in enumerate(ledgers, start=1):
        assert led["down"] == tr.bytes_down_per_round
        assert led["up"] == tr.bytes_up_per_round
        assert led["cum_down"] == i * tr.bytes_down_per_round
        assert led["cum_up"] == i * tr.bytes_up_per_round
    assert ledgers[-1]["cum_total"] == tr.total_bytes

    # run_config ledger carries the engine dispatch's own attrs
    cfg = mem.named("run_config")[0]["values"]
    assert cfg["engine"] == "sync" and cfg["agg_block_n"] == 2048
    assert cfg["k_simple"] == 4 and cfg["n_chunks_complex"] == 2


def test_padding_counter_counts_weight0_slots():
    """k=3 per population at chunk 2 -> one zero-validity padding slot
    per population per round."""
    mem = obslib.MemorySink()
    fed = FedConfig(n_devices=6, n_simple=3, participation=1.0,
                    local_epochs=1, lr=0.1, batch_size=4,
                    algorithm="fedhen", seed=0, cohort_chunk=2)
    tr = FederatedTrainer(_ToyAdapter(), fed, _shards(6),
                          telemetry=obslib.Telemetry([mem]))
    tr.run_round()
    assert mem.named("padding_weight0_clients")[0]["value"] == 2


def test_nan_exclusion_counter():
    """A NaN-poisoned client shows up as nan_excluded_devices > 0 in the
    round it is sampled (participation=1.0 -> every round)."""
    mem = obslib.MemorySink()
    tr = _make_trainer(obslib.Telemetry([mem]), chunk=1, poison=1)
    tr.run_round()
    tr.run_round()
    values = [e["value"] for e in mem.named("nan_excluded_devices")]
    assert values == [1, 1]
    for leaf in jax.tree.leaves(tr.server.complex):
        assert np.isfinite(np.asarray(leaf)).all()


# ---------------------------------------------------------------------------
# Async engine: staleness histogram, cache counters, version-aware bytes
# ---------------------------------------------------------------------------

def test_async_lag1_span_tree_and_health():
    mem = obslib.MemorySink()
    tr = _make_trainer(obslib.Telemetry([mem]), async_lag=1)
    tr.run_round()
    tr.run_round()

    rounds = [e for e in mem.named("round")]
    assert [e["engine"] for e in rounds] == ["async", "async"]
    assert [e["lag"] for e in rounds] == [1, 1]
    # the same timed host phases as the synchronous engine
    for r in (0, 1):
        spans = [e for e in mem.of_kind("span")
                 if e["round"] == r and e["name"] not in
                 ("trace_lower", "compile")]
        assert [e["path"] for e in spans] == \
            ["round/sample_gather", "round/execute", "round"]
        assert all(e["dur_s"] > 0 for e in spans)
    assert [e["compiles"] > 0 for e in rounds] == [True, False]

    # staleness histogram matches the fold schedule exactly:
    # round 0 clamps to all-fresh; round 1 has one 1-stale chunk (the
    # first of the fold stream: the simple population's first chunk)
    hists = [e["values"] for e in mem.named("staleness_hist")]
    assert hists == [{"0": 4}, {"0": 3, "1": 1}]
    assert [list(s) for s in tr.async_engine.schedule(1)] == \
        [[1, 0], [0, 0]]

    # version-cache counters: round 0 all misses (8 clients); round 1
    # the stale chunk's clients (chunk=2) re-use their held version
    assert [e["value"] for e in mem.named("version_cache_miss")] == [8, 6]
    assert [e["value"] for e in mem.named("version_cache_hit")] == [0, 2]

    # byte ledger equals the engine's version-aware accounting
    eng = tr.async_engine
    led = [e["values"] for e in mem.named("comm_bytes")]
    assert led[-1]["down"] == eng.last_bytes_down
    assert led[-1]["up"] == eng.last_bytes_up
    assert led[-1]["cum_down"] == tr.total_bytes_down
    assert led[-1]["cum_total"] == tr.total_bytes
    # the stale chunk saved exactly its clients' downloads in round 1
    assert led[1]["down"] == led[0]["down"] - 2 * tr.per_simple_bytes


# ---------------------------------------------------------------------------
# Spans on the profiler's clock
# ---------------------------------------------------------------------------

def test_spans_are_profiler_annotations(tmp_path):
    """Two profiled rounds: the program's spans are host events of the
    ``.xplane.pb``, ``execute`` inside ``round``, and the second round
    compiled nothing."""
    import glob
    mem = obslib.MemorySink()
    tr = _make_trainer(obslib.Telemetry([mem]))
    with jax.profiler.trace(str(tmp_path)):
        tr.run_round()
        tr.run_round()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    for name in ("round", "sample_gather", "execute"):
        assert len(events.get(name, [])) == 2, (name, sorted(events))
    for (r0, r1), (x0, x1) in zip(sorted(events["round"]),
                                  sorted(events["execute"])):
        assert r0 <= x0 < x1 <= r1
    assert [e["compiles"] for e in mem.named("round")][1] == 0


def test_spans_without_jax_stay_plain(tmp_path):
    """In a process that never loaded jax, an enabled span neither
    imports it nor carries ``compiles``."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    code = (
        "import sys\n"
        "from repro.obs import telemetry as t\n"
        "m = t.MemorySink()\n"
        "with t.Telemetry([m]).span('round'):\n"
        "    pass\n"
        "e, = m.events\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "assert 'compiles' not in e and e['dur_s'] >= 0, e\n")
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env)
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# Device stages: tags on the round jit's ops
# ---------------------------------------------------------------------------

def _stage_counts(hlo: str) -> dict:
    import re
    counts: dict = {}
    for stage in re.findall(r'fedhen_scope="(\w+)"', hlo):
        counts[stage] = counts.get(stage, 0) + 1
    return counts


def _stripped(hlo: str) -> list:
    """Compiled HLO text without metadata, frontend attributes or the
    instructions' own names: what the executable computes."""
    import re
    names: dict = {}
    out = []
    for ln in hlo.splitlines():
        if not re.match(r"\s*(ROOT )?%|ENTRY |\}", ln):
            continue
        ln = re.sub(r", (frontend_attributes|metadata)=\{[^{}]*\}", "", ln)
        ln = re.sub(r"%([\w.-]+)",
                    lambda m: "%" + names.setdefault(m.group(1),
                                                     f"v{len(names)}"), ln)
        out.append(ln)
    return out


@pytest.mark.parametrize("fed_kw", [
    {}, {"async_lag": 1}, {"comm_dtype": "int8"},
    {"variance_reduction": "scaffold"}],
    ids=["sync", "async", "int8", "scaffold"])
def test_round_stages_tag_the_compiled_round(fed_kw):
    """Every engine's round carries the stages it runs; the wire stage
    holds ops only where the wire is not the identity."""
    hlo = _make_trainer(None, **fed_kw).lower_round().compile().as_text()
    counts = _stage_counts(hlo)
    want = {"local_sgd", "fold", "finalize"}
    if fed_kw.get("comm_dtype") == "int8":
        want.add("wire")
    assert set(counts) == want, counts


@pytest.mark.parametrize("async_lag", [0, 1])
def test_stage_tags_leave_the_compiled_round_unchanged(async_lag,
                                                      monkeypatch):
    """With the stages made no-ops, the compiled round is the same
    instruction for instruction once metadata is stripped: the tags
    change no fusion and no schedule."""
    import contextlib
    from repro.core import federated
    tagged = _make_trainer(None, async_lag=async_lag).lower_round()
    tagged = tagged.compile().as_text()
    no_stage = lambda name: contextlib.nullcontext()
    monkeypatch.setattr(federated, "stage", no_stage)
    monkeypatch.setattr(async_rounds, "stage", no_stage)
    plain = _make_trainer(None, async_lag=async_lag).lower_round()
    plain = plain.compile().as_text()
    assert _stage_counts(tagged) and _stage_counts(plain) == {}
    assert _stripped(tagged) == _stripped(plain)


def _musicgen_trainer():
    from repro import configs
    from repro.core.adapters import LMAdapter
    from repro.data.synthetic import synthetic_conditioning, synthetic_lm
    cfg = configs.get_reduced("musicgen-large")
    fe = cfg.frontend
    data = synthetic_lm(4, 8, cfg.vocab_size, n_codebooks=cfg.n_codebooks)
    data.update(synthetic_conditioning(4, fe.n_tokens, fe.d_in))
    shards = [{k: jnp.asarray(v[2 * i:2 * i + 2]) for k, v in data.items()
               if k != "labels"} for i in range(2)]
    fed = FedConfig(n_devices=2, n_simple=1, participation=1.0,
                    local_epochs=1, lr=0.1, batch_size=2,
                    algorithm="fedhen", seed=0)
    return FederatedTrainer(LMAdapter(cfg), fed, shards)


def test_part_tags_leave_the_compiled_round_unchanged(monkeypatch):
    """With the client model's parts made no-ops, the compiled round of
    MusicGen's block is the same instruction for instruction once
    metadata is stripped; with them every part tags ops."""
    import contextlib
    import re
    from repro.models import transformer
    tagged = _musicgen_trainer().lower_round().compile().as_text()
    monkeypatch.setattr(transformer, "part",
                        lambda name: contextlib.nullcontext())
    plain = _musicgen_trainer().lower_round().compile().as_text()
    parts = set(re.findall(r'fedhen_part="(\w+)"', tagged))
    assert parts == {"self_attn", "cross_attn", "ffn", "heads"}
    assert "fedhen_part" not in plain
    assert _stripped(tagged) == _stripped(plain)


def test_stage_names_are_the_four():
    from repro.obs import scopes
    assert scopes.STAGES == ("local_sgd", "wire", "fold", "finalize")
    with pytest.raises(ValueError, match="unknown stage"):
        with scopes.stage("train"):
            pass
    with pytest.raises(ValueError, match="unknown part"):
        with scopes.part("local_sgd"):
            pass


# ---------------------------------------------------------------------------
# The observation contract: sinks never steer the run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("async_lag", [0, 1])
def test_noop_sink_run_bit_identical_to_telemetry_off(async_lag):
    off = _make_trainer(None, async_lag=async_lag)
    on = _make_trainer(obslib.Telemetry([obslib.NullSink()]),
                       async_lag=async_lag)
    m_off = [off.run_round() for _ in range(2)]
    m_on = [on.run_round() for _ in range(2)]
    assert m_off == m_on
    assert _max_abs_diff(off.server.complex, on.server.complex) == 0.0
    assert off.total_bytes == on.total_bytes


# ---------------------------------------------------------------------------
# run() logging + JSONL -> report pipeline
# ---------------------------------------------------------------------------

def test_run_log_line_format_bit_identical(capsys):
    """The legacy log line routed through a StdoutSink prints exactly
    the string the pre-telemetry log callback received."""
    legacy = []
    off = _make_trainer(None)
    off.run(2, eval_every=1, test_batch={"x": jnp.zeros((4, 4))},
            log=legacy.append)
    on = _make_trainer(obslib.Telemetry([obslib.StdoutSink()]))
    capsys.readouterr()
    on.run(2, eval_every=1, test_batch={"x": jnp.zeros((4, 4))})
    printed = capsys.readouterr().out.splitlines()
    assert printed == legacy
    assert all(line.startswith("round ") for line in printed)


def test_jsonl_roundtrip_and_report(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tel = obslib.Telemetry([obslib.JsonlSink(path)])
    tr = _make_trainer(tel)
    tr.run(2, eval_every=1, test_batch={"x": jnp.zeros((4, 4))})
    tel.close()

    events = obslib.read_jsonl(path)
    assert events, "JSONL run log is empty"
    kinds = {e["kind"] for e in events}
    assert kinds >= {"span", "counter", "ledger", "log"}

    summary = obs_report.summarize(events)
    assert summary["rounds"]["n_rounds"] == 2
    assert summary["comm"]["cum_total"] == tr.total_bytes
    assert summary["health"]["nan_excluded_devices"] == 0
    assert summary["rounds"]["compile_s"] > 0
    # only the first round compiled
    assert summary["rounds"]["compiles"] >= 1
    assert list(summary["rounds"]["compiling_rounds"]) == [0]
    # eval ledgers feed the trajectory; acc metrics count as reached
    # at-or-ABOVE the target, so an unreachable ceiling stays None
    summary_t = obs_report.summarize(events, target=1e9,
                                     target_metric="acc_simple")
    assert summary_t["progress"]["rounds_to_target"] is None
    rendered = obs_report.render(summary)
    for needle in ("telemetry run report", "-- rounds --", "-- comm --",
                   "-- client health --", "backend compiles: "):
        assert needle in rendered
    # the CLI entry point renders the same file without error
    assert "rounds: 2" in obs_report.report_path(path)


def test_report_rounds_to_target():
    """rounds_to_target: first eval round at or under the threshold."""
    events = [
        {"kind": "ledger", "name": "eval", "round": 1,
         "values": {"loss_complex": 0.9}},
        {"kind": "ledger", "name": "eval", "round": 2,
         "values": {"loss_complex": 0.4}},
        {"kind": "ledger", "name": "eval", "round": 3,
         "values": {"loss_complex": 0.2}},
    ]
    s = obs_report.summarize(events, target=0.5)
    assert s["progress"]["rounds_to_target"] == 2
    assert s["progress"]["final"] == 0.2
    s2 = obs_report.summarize(events, target=0.05)
    assert s2["progress"]["rounds_to_target"] is None


def test_report_rounds_to_target_acc_direction():
    """acc* metrics flip the comparison: reached at-or-ABOVE the target."""
    events = [
        {"kind": "ledger", "name": "eval", "round": 1,
         "values": {"acc_simple": 0.1}},
        {"kind": "ledger", "name": "eval", "round": 2,
         "values": {"acc_simple": 0.3}},
        {"kind": "ledger", "name": "eval", "round": 3,
         "values": {"acc_simple": 0.6}},
    ]
    s = obs_report.summarize(events, target=0.25,
                             target_metric="acc_simple")
    assert s["progress"]["rounds_to_target"] == 2
    s2 = obs_report.summarize(events, target=0.9,
                              target_metric="acc_simple")
    assert s2["progress"]["rounds_to_target"] is None


def test_compare_summaries_and_render():
    """--compare diff: config differences listed, per-section a/b/delta
    rows computed B - A, rounds-to-target delta included."""
    def events(vr, down, loss2):
        return [
            {"kind": "ledger", "name": "run_config",
             "values": {"algorithm": "fedhen", "variance_reduction": vr}},
            {"kind": "span", "name": "round", "round": 0, "dur_s": 0.5},
            {"kind": "span", "name": "round", "round": 1, "dur_s": 0.5},
            {"kind": "ledger", "name": "comm_bytes", "round": 1,
             "values": {"down": down, "up": down, "cum_down": 2 * down,
                        "cum_up": 2 * down, "cum_total": 4 * down}},
            {"kind": "ledger", "name": "eval", "round": 1,
             "values": {"loss_complex": 0.9}},
            {"kind": "ledger", "name": "eval", "round": 2,
             "values": {"loss_complex": loss2}},
        ]

    a = obs_report.summarize(events("none", 100.0, 0.6), target=0.5)
    b = obs_report.summarize(events("scaffold", 200.0, 0.4), target=0.5)
    cmp = obs_report.compare_summaries(a, b)
    assert cmp["config_diff"] == {
        "variance_reduction": {"a": "none", "b": "scaffold"}}
    assert cmp["comm"]["bytes_down_per_round"]["delta"] == 100.0
    assert cmp["comm"]["cum_total"]["delta"] == 400.0
    # A never reaches 0.5; B reaches it at round 2
    rt = cmp["progress"]["rounds_to_target"]
    assert rt["a"] is None and rt["b"] == 2 and rt["delta"] is None
    assert cmp["progress"]["final"]["delta"] == pytest.approx(-0.2)
    assert cmp["phases"]["round"]["delta"] == pytest.approx(0.0)

    rendered = obs_report.render_compare(cmp)
    for needle in ("telemetry run comparison", "config differences",
                   "variance_reduction: A=none  B=scaffold",
                   "-- comm --", "rounds_to_target"):
        assert needle in rendered


def test_compare_paths_cli(tmp_path):
    """The file-level entry point diffs two JSONL logs end to end."""
    import subprocess
    import sys

    def write(path, down):
        with open(path, "w") as f:
            for e in (
                    {"kind": "ledger", "name": "run_config",
                     "values": {"algorithm": "fedhen"}},
                    {"kind": "ledger", "name": "comm_bytes", "round": 0,
                     "values": {"down": down, "up": down,
                                "cum_total": 2 * down}}):
                f.write(json.dumps(e) + "\n")

    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    write(pa, 100.0)
    write(pb, 300.0)
    out = obs_report.compare_paths(pa, pb)
    assert "bytes_down_per_round" in out and "+200" in out

    proc = subprocess.run(
        [sys.executable, "tools/obs_report.py", "--compare", pa, pb],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "telemetry run comparison" in proc.stdout
