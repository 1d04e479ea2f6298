"""The reduction under the program's own names on a hand-built trace: two
iterations of the loop, four executables, a ``while`` with no path of its own
over a body under ``rssm_scan``, a fusion under no scope, idle gaps inside the
action fetch, inside ``bookkeeping`` and under nothing, and an execution that
the trace's end cut.  Then the readers: each finds its number in a reduction
and reads ``None``, never raising, on a run that has no trace or no counter."""

import json
import os

import pytest

from benchmarks.chip import span_reduce
from benchmarks.chip.manifest import ROOT, Manifest, check_names
from benchmarks.chip.span_reduce import FETCH_SPAN, reduce_spans, scope_of
from benchmarks.chip.trace_reduce import UNATTRIBUTED, idle_by_span, owner_segments
from test_bench_manifest import _run

MS = 1_000_000
# the per-layer entries whose readers go through this reduction (PR 27's 18)
ENTRIES = [m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]
           if "span_reduce" in open(os.path.join(ROOT, "benchmarks", "chip", "metrics", m["name"] + ".py")).read()]
FAMILY = Manifest(ROOT).family(Manifest(ROOT).config("dv3_s"))
EXECUTABLES, SCOPES = FAMILY.executables, FAMILY.train_step_scopes


def _iteration(o):
    """Device events and host spans of one 100 ms iteration that starts at ``o`` ms."""
    ops = [
        ("%copy.17", o + 0, 20, "jit(replay_gather)/gather"),
        ("%fusion.1", o + 20, 4, "jit(train_step)/jvp(encoder)/WorldModel.encode/cnn_encoder/conv_general_dilated"),
        ("%while.1", o + 24, 12, ""),  # no path of its own, as on the v5e
        ("%fusion.2", o + 25, 2, "jit(train_step)/WorldModel.dynamic/rssm.dynamic/eq"),  # a hoisted name: no scope
        ("%fusion.3", o + 27, 3, "jit(train_step)/transpose(jvp(rssm_scan))/while/body/closed_call/mul"),
        ("%fusion.4", o + 36, 4, "jit(train_step)/jvp()/reduce_sum"),  # under no scope
        ("%fusion.5", o + 41, 5, "jit(train_step)/jvp(decoder_heads)/WorldModel.decode/cnn_decoder/conv_general_dilated"),
        ("%fusion.6", o + 46, 4, "jit(train_step)/optim/sqrt"),
        ("%fusion.7", o + 50, 2, "jit(player_step)/Actor.act/dot_general"),
        ("%scatter.1", o + 54, 1, "jit(replay_add)/scatter"),
        ("%convert.1", o + 76, 2, "jit(convert_element_type)/convert_element_type"),
    ]
    modules = [
        ("jit_replay_gather(11)", o + 0, 20), ("jit_train_step(22)", o + 20, 30), ("jit_player_step(33)", o + 50, 2),
        ("jit_replay_add(44)", o + 54, 1), ("jit_convert_element_type(55)", o + 76, 2),
    ]
    spans = [
        ("sheeprl/rollout", o + 0, 60), ("sheeprl/rollout/action-fetch", o + 10, 45), ("sheeprl/env_step_async", o + 56, 2),
        ("sheeprl/buffer-sample", o + 62, 4), ("sheeprl/train", o + 66, 4), ("sheeprl/env_wait", o + 70, 2),
        ("sheeprl/bookkeeping", o + 72, 8),
    ]
    return ops, modules, spans


def _trace(devices=1, spans=True):
    ops, modules, host = [("%reshape.0", -5, 1, "")], [("jit_reshape(1)", -5, 1)], [("PjitFunction(train_step)", 0, 1)]
    for o in (0, 100):
        more = _iteration(o)
        ops, modules, host = ops + more[0], modules + more[1], host + (more[2] if spans else [])
    # the third iteration starts and the trace ends in the middle of its gather
    ops.append(("%copy.17", 200, 10, "jit(replay_gather)/gather"))
    modules.append(("jit_replay_gather(11)", 200, 10))
    if spans:
        host.append(("sheeprl/rollout", 200, 10))
    scaled = lambda events: [(e[0], e[1] * MS, e[2] * MS) + tuple(e[3:] or ("",)) for e in events]  # noqa: E731
    planes = [
        {"name": f"/device:TPU:{i}", "lines": [{"name": "XLA Ops", "events": scaled(ops)}, {"name": "XLA Modules", "events": scaled(modules)}]}
        for i in range(devices)
    ]
    planes.append({"name": "/host:CPU", "lines": [{"name": "python", "events": scaled(host)}]})
    return {"planes": planes}


@pytest.mark.parametrize("devices", [1, 4])
def test_executables_scopes_and_idle_classes(devices):
    out = reduce_spans(_trace(devices), EXECUTABLES, SCOPES)
    # the gather the trace's end cut is left out: (20 + 20 + 10) / 3 would read 16.7
    assert out["module_ms"] == pytest.approx({"replay_gather": 20.0, "train_step": 29.0, "player": 2.0, "replay_add": 1.0})
    assert out["module_runs"] == {"replay_gather": 2 * devices, "train_step": 2 * devices, "player": 2 * devices, "replay_add": 2 * devices}
    assert out["scope_ms"] == pytest.approx({"encoder": 4.0, "rssm_scan": 12.0, "decoder_heads": 5.0, "imagination": 0.0,
                                             "behaviour_losses": 0.0, "optim": 4.0, "unscoped": 4.0})
    assert sum(out["scope_ms"].values()) == pytest.approx(out["module_ms"]["train_step"])
    assert out["iterations"] == 2 * devices
    assert out["idle_ms"] == pytest.approx({
        FETCH_SPAN: 3.0,  # the gap inside the train step and the one before the ring's add
        "sheeprl/rollout": 3.0, "sheeprl/env_step_async": 2.0, "sheeprl/buffer-sample": 4.0, "sheeprl/train": 4.0,
        "sheeprl/env_wait": 2.0, "sheeprl/bookkeeping": 6.0, UNATTRIBUTED: 22.0,
    })
    assert sum(out["idle_ms"].values()) == pytest.approx(100.0 - (20 + 29 + 2 + 1 + 2))


def test_a_trace_without_the_programs_names_reads_nothing():
    out = reduce_spans(_trace(spans=False), EXECUTABLES, SCOPES)
    assert out["idle_ms"] is None and out["iterations"] == 0 and out["module_ms"]["train_step"] == pytest.approx(29.0)
    bare = _trace()
    for plane in bare["planes"]:
        for line in plane["lines"]:
            line["events"] = [(n.replace("replay_gather", "_gather_all").replace("player_step", "_step"), s, d,
                               "jit(train_step)/jvp(WorldModel.encode)/cnn_encoder/conv" if p else "") for n, s, d, p in line["events"]]
    out = reduce_spans(bare, EXECUTABLES, SCOPES)  # the parent's trace: paths, and no scope or executable of these names
    assert out["scope_ms"] is None and "replay_gather" not in out["module_ms"] and "player" not in out["module_ms"]
    assert reduce_spans({"planes": []}, EXECUTABLES, SCOPES) == {"module_ms": {}, "module_runs": {}, "scope_ms": None, "idle_ms": None, "iterations": 0}


def test_a_scope_is_a_component_of_the_path_forward_or_backward():
    assert scope_of("jit(train_step)/transpose(jvp(rssm_scan))/while/body/mul", SCOPES) == "rssm_scan"
    assert scope_of("jit(train_step)/jvp(encoder)/WorldModel.encode/cnn_encoder/conv", SCOPES) == "encoder"
    assert scope_of("jit(train_step)/jvp(decoder_heads)/WorldModel.decode/cnn_decoder/conv", SCOPES) == "decoder_heads"
    assert scope_of("jit(train_step)/optim/sqrt", SCOPES) == "optim"
    assert scope_of("jit(train_step)/jvp()/WorldModel.encode/cnn_encoder/conv", SCOPES) == "unscoped"
    assert scope_of("jit(train_step)/jvp(imagination)/while/body/optimizer_like/x", SCOPES) == "imagination"
    assert scope_of("", SCOPES) == "unscoped"
    # a family that names other scopes, or none, splits by those
    assert scope_of("jit(train_step)/jvp(delta_rule)/while/body/mul", ("attention", "delta_rule")) == "delta_rule"
    assert scope_of("jit(train_step)/jvp(encoder)/conv", ()) == "unscoped"


def test_the_reducers_own_scopes_are_a_leftover_that_says_what_the_family_says():
    """``span_reduce.SCOPES`` and the one-argument ``scope_of`` stay for one test outside the
    benchmark's directories; until it is repointed they must not drift from the family's answer."""
    assert span_reduce.SCOPES == SCOPES
    assert scope_of("jit(train_step)/optim/sqrt") == scope_of("jit(train_step)/optim/sqrt", SCOPES) == "optim"
    with pytest.raises(TypeError):
        reduce_spans({"planes": []}, EXECUTABLES)  # the reduction itself takes its scopes from the caller


def test_the_innermost_span_owns_its_time():
    spans = [("sheeprl/rollout", 0, 60), ("sheeprl/rollout/action-fetch", 10, 55), ("sheeprl/bookkeeping", 72, 80)]
    assert owner_segments(spans) == [(0, 10, "sheeprl/rollout"), (10, 55, "sheeprl/rollout/action-fetch"),
                                     (55, 60, "sheeprl/rollout"), (72, 80, "sheeprl/bookkeeping")]
    idle = idle_by_span([(5, 12), (58, 75), (90, 95)], owner_segments(spans))
    assert idle == {"sheeprl/rollout": 5 + 2, "sheeprl/rollout/action-fetch": 2, "sheeprl/bookkeeping": 3, UNATTRIBUTED: 12 + 5}


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------
def test_the_entries_of_this_reduction_each_have_a_reader():
    manifest = Manifest(ROOT)
    assert len(ENTRIES) == 18 and check_names({"per_layer": ENTRIES}) == []
    e2e = {m["name"] for m in manifest.data["end_to_end"]}
    for entry in ENTRIES:
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert entry["moves"] in e2e and entry["better"] == "lower"
        assert entry["workloads"] == ["dv3_s.hbm_replay"] and entry["source"] in ("program_counter", "device_trace")
        assert callable(manifest.reader(entry["name"]))


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_a_reader_reads_none_where_there_is_nothing_to_read(entry):
    read = Manifest(ROOT).reader(entry["name"])
    assert read({**_run(False), "scrapes": [{}, {}]}) is None  # not traced, no such counter
    assert read(_run(True)) is None  # traced by the harness, but no trace file and no scrape to be found
    assert read({**_run(True), "cell": {"name": "no_such.cell"}, "scrapes": [{"sheeprl_env_steps_total": 1.0}] * 2}) is None


def test_the_readers_find_their_numbers(monkeypatch):
    reduced = reduce_spans(_trace(), EXECUTABLES, SCOPES)
    monkeypatch.setattr(span_reduce, "for_run", lambda run: reduced)
    phase = 'sheeprl_phase_seconds_total{phase="%s"}'
    calls = 'sheeprl_instrumented_calls_total{fn="train_step"}'
    s0 = {"sheeprl_env_steps_total": 1000.0, calls: 990.0, phase % "rollout/action-fetch": 50.0, phase % "rollout/replay-add": 3.0,
          phase % "bookkeeping": 1.0, phase % "buffer-sample": 2.0, phase % "train": 5.0}
    s1 = {"sheeprl_env_steps_total": 1500.0, calls: 1490.0, phase % "rollout/action-fetch": 74.0, phase % "rollout/replay-add": 4.5,
          phase % "bookkeeping": 1.25, phase % "buffer-sample": 3.0, phase % "train": 8.0}
    run = {**_run(True), "scrapes": [s0, s1]}
    manifest = Manifest(ROOT)
    got = {entry["name"]: manifest.reader(entry["name"])(run) for entry in ENTRIES}
    assert got == pytest.approx({
        "loop.action_fetch_wait_ms": 48.0, "loop.replay_add_host_ms": 3.0, "loop.bookkeeping_host_ms": 0.5,
        "loop.sample_host_ms": 2.0, "loop.train_dispatch_ms": 6.0,
        "replay.gather_device_ms": 20.0, "replay.add_device_ms": 1.0, "player.forward_device_ms": 2.0,
        "kernels.encoder_ms": 4.0, "kernels.rssm_scan_ms": 12.0, "kernels.decoder_heads_ms": 5.0, "kernels.imagination_ms": 0.0,
        "kernels.behaviour_losses_ms": 0.0, "kernels.optim_ms": 4.0, "kernels.unscoped_ms": 4.0,
        "device.idle_in_fetch_ms": 3.0, "device.idle_in_host_work_ms": 21.0, "device.idle_unattributed_pct": 100 * 22.0 / 46.0,
    })


def test_a_run_is_reduced_from_where_the_command_keeps_its_trace(monkeypatch, tmp_path):
    """``for_run`` looks under ``run.WORK_DIR/<cell>/trace`` and loads the file once."""
    from benchmarks.chip import run as command

    monkeypatch.setattr(command, "WORK_DIR", str(tmp_path))
    profile = tmp_path / "some.cell" / "trace" / "plugins" / "profile" / "2026_01_01"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(b"")  # an empty XSpace: no plane
    loads = []
    monkeypatch.setattr(span_reduce, "load_spans", lambda path: loads.append(path) or _trace())
    span_reduce._reduced.cache_clear()
    run = {**_run(True), "cell": {"name": "some.cell"}, "family": FAMILY}
    assert span_reduce.module_ms({**run, "family": None}, "replay_gather") is None  # no family, no names to look for
    assert span_reduce.module_ms(run, "replay_gather") == pytest.approx(20.0)
    assert span_reduce.scope_ms(run, "rssm_scan") == pytest.approx(12.0)
    assert span_reduce.idle_ms(run)[FETCH_SPAN] == pytest.approx(3.0)
    assert loads == [str(profile / "host.xplane.pb")]
    span_reduce._reduced.cache_clear()


def test_an_xplane_file_is_read_with_its_metadata_stats(tmp_path):
    """The loader against a file the protobuf runtime wrote from the same subset of the schema."""
    space = span_reduce._xspace_class()()
    device = space.planes.add(name=b"/device:TPU:0")
    device.stat_metadata.add(key=1).value.name = b"tf_op"
    device.stat_metadata.add(key=2).value.name = b"jit(train_step)/optim/sqrt"
    meta = device.event_metadata.add(key=7)
    meta.value.name = b"%fusion.1 = f32[] fusion()"
    meta.value.stats.add(metadata_id=1, str_value=b"jit(train_step)/jvp(encoder)/conv")
    meta = device.event_metadata.add(key=8)
    meta.value.name = b"%fusion.2 = f32[] fusion()"
    meta.value.stats.add(metadata_id=1, ref_value=2)
    device.event_metadata.add(key=9).value.name = b"jit_train_step(5)"
    line = device.lines.add(name=b"XLA Ops", timestamp_ns=1000)
    line.events.add(metadata_id=7, offset_ps=2_000_000, duration_ps=3_000_000)
    line.events.add(metadata_id=8, offset_ps=5_000_000, duration_ps=1_500_000)
    device.lines.add(name=b"XLA Modules", timestamp_ns=1000).events.add(metadata_id=9, offset_ps=2_000_000, duration_ps=4_500_000)
    device.lines.add(name=b"Steps", timestamp_ns=1000).events.add(metadata_id=9, offset_ps=0, duration_ps=1)
    host = space.planes.add(name=b"/host:CPU")
    host.event_metadata.add(key=1).value.name = b"sheeprl/rollout"
    host.event_metadata.add(key=2).value.name = b"PjitFunction(train_step)"
    line = host.lines.add(name=b"python", timestamp_ns=500)
    line.events.add(metadata_id=1, offset_ps=1_000_000, duration_ps=9_000_000)
    line.events.add(metadata_id=2, offset_ps=2_000_000, duration_ps=1_000_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert span_reduce.load_spans(str(path)) == {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [("%fusion.1 = f32[] fusion()", 3000, 3000, "jit(train_step)/jvp(encoder)/conv"),
                                           ("%fusion.2 = f32[] fusion()", 6000, 1500, "jit(train_step)/optim/sqrt")]},
            {"name": "XLA Modules", "events": [("jit_train_step(5)", 3000, 4500, "")]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [("sheeprl/rollout", 1500, 9000, "")]}]},
    ]}
