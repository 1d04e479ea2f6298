"""A step of scans within scans, split by scope: every operation counts for itself."""

import pytest

from benchmarks.chip import lm_reduce
from benchmarks.chip.lm_reduce import UNSCOPED, reduce_scopes, self_ns_by_scope
from benchmarks.chip.manifest import ROOT, Manifest

MS = 1_000_000
FAMILY = Manifest(ROOT).family(Manifest(ROOT).config("olmo_hybrid_7b"))
SCOPES = FAMILY.train_step_scopes


def test_the_familys_scopes_are_the_programs():
    from sheeprl_tpu.models.hybrid_lm import SCOPES as program_scopes

    assert tuple(SCOPES) == tuple(program_scopes)


def _update(o):
    """Device events of one 100 ms update that starts at ``o`` ms: a scan over
    minibatches around everything, the delta rule's scan inside it."""
    ev = lambda name, start, dur, path="": (name, (o + start) * MS, dur * MS, path)  # noqa: E731
    return [
        ev("%while.epochs", 0, 100),
        ev("%while.minibatches", 1, 98),
        ev("%gather.1", 2, 3, "jit(update)/while/body/while/body/gather"),
        ev("%fusion.embed", 5, 2, "jit(update)/jvp(HybridLM)/embed/take"),
        ev("%fusion.proj", 7, 10, "jit(update)/jvp(HybridLM)/layers_0/mixer/delta_rule_proj/q_proj/dot_general"),
        ev("%while.chunks", 17, 20),  # no path of its own, as on the v5e
        ev("%fusion.chunk", 18, 6, "jit(update)/jvp(HybridLM)/layers_0/mixer/delta_rule/while/body/dot_general"),
        ev("%fusion.chunk", 25, 6, "jit(update)/jvp(HybridLM)/layers_0/mixer/delta_rule/while/body/dot_general"),
        ev("%fusion.mlp", 37, 30, "jit(update)/transpose(jvp(HybridLM))/layers_0/swiglu/mlp/down_proj/dot_general"),
        ev("%fusion.attn", 67, 8, "jit(update)/checkpoint/HybridLM/layers_3/mixer/full_attention/dot_general"),
        ev("%fusion.head", 75, 9, "jit(update)/jvp(HybridLM)/vocab_head/lm_head/dot_general"),
        ev("%fusion.loss", 84, 3, "jit(update)/jvp(ppo_loss)/log_softmax"),
        ev("%fusion.adam", 87, 10, "jit(update)/while/body/while/body/optim/mul"),
    ]


def _trace():
    ops = [("%fusion.player", 0, 5 * MS, "jit(policy_step)/HybridLM/embed/take")]
    modules = [("jit_policy_step(1)", 0, 5 * MS, "")]
    for o in (10, 120, 230):
        ops += _update(o)
        modules.append((f"jit_update({o})", o * MS, 100 * MS, ""))
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                                             {"name": "XLA Modules", "events": modules}]}]}


def test_an_operation_counts_for_itself_and_a_while_for_its_overhead():
    ops = sorted(_update(0), key=lambda e: (e[1], -e[2]))
    got = {k: v / MS for k, v in self_ns_by_scope(ops, 0, 100 * MS, SCOPES).items()}
    assert got == {"embed": 2, "delta_rule_proj": 10, "delta_rule": 12, "swiglu": 30, "full_attention": 8, "vocab_head": 9,
                   "ppo_loss": 3, "optim": 10,
                   # the epochs' loop 2, the minibatches' 98 - 95, the chunks' 20 - 12, the gather 3
                   UNSCOPED: 2 + 3 + 8 + 3}
    assert sum(got.values()) == 100  # the buckets sum to the step's busy time


def test_the_execution_at_the_traces_edge_is_left_out():
    reduced = reduce_scopes(_trace(), "jit_update", SCOPES)
    assert reduced["swiglu"] == 30.0 and reduced["delta_rule"] == 12.0 and reduced[UNSCOPED] == 16.0  # the middle update alone
    assert reduce_scopes(_trace(), "jit_train_step", SCOPES) is None  # another family's executable: nothing to read
    assert reduce_scopes(_trace(), "jit_update", ("encoder",)) is None  # a program without these scopes


def test_the_readers_read_none_where_there_is_nothing_to_read():
    manifest = Manifest(ROOT)
    config = manifest.config("olmo_hybrid_7b")
    names = [m["name"] for m in manifest.metrics_for("olmo_hybrid_7b.token_ppo_32x256", "per_layer") if "workloads" in m]
    assert len(names) == 15 and all(n.split(".")[0] in ("lm", "policy", "seq") for n in names)
    untraced = {"trace": None, "family": FAMILY, "config": config, "cell": {"name": "no_such.cell"}, "scrapes": [{}, {}],
                "device": {"kind": "TPU v5 lite", "count": 1}}
    traced_elsewhere = {**untraced, "trace": {"busy_s": 1.0}}
    for name in names:
        assert manifest.reader(name)(untraced) is None and manifest.reader(name)(traced_elsewhere) is None, name


def test_the_readers_find_their_numbers(monkeypatch):
    reduced = reduce_scopes(_trace(), "jit_update", SCOPES)
    monkeypatch.setattr(lm_reduce, "_reduced", lambda *a: reduced)
    monkeypatch.setattr(lm_reduce, "find_xplane", lambda path: "x")
    manifest = Manifest(ROOT)
    config = manifest.config("olmo_hybrid_7b")
    run = {"trace": {"busy_s": 1.0}, "family": FAMILY, "config": config, "cell": {"name": "c"}, "device": {"kind": "TPU v5 lite", "count": 1}}
    assert manifest.reader("lm.swiglu_ms")(run) == 30.0 and manifest.reader("lm.unscoped_ms")(run) == 16.0
    # 16,384 tokens x 15 heads x 3 layers of recurrent-form work: bytes-bound, 4.16 ms; over the scope's 12 ms
    work = FAMILY.delta_rule_work(config)
    assert work["bytes"] / 819e9 > work["flops"] / 197e12
    assert manifest.reader("lm.delta_rule_roofline_pct")(run) == pytest.approx(100 * (work["bytes"] / 819e9) / 0.012)
    assert 30 < manifest.reader("lm.delta_rule_roofline_pct")(run) < 40
    calls = 'sheeprl_phase_calls_total{phase="rollout/action-fetch"}'
    seconds = 'sheeprl_phase_seconds_total{phase="%s"}'
    s0 = {calls: 100.0, seconds % "rollout": 1.0, seconds % "rollout/action-fetch": 0.5, seconds % "train": 0.1,
          'sheeprl_instrumented_calls_total{fn="train_step"}': 1.0}
    s1 = {calls: 356.0, seconds % "rollout": 3.56, seconds % "rollout/action-fetch": 2.292, seconds % "train": 0.3,
          'sheeprl_instrumented_calls_total{fn="train_step"}': 3.0}
    run["scrapes"] = [s0, s1]
    assert manifest.reader("seq.rollout_host_ms")(run) == pytest.approx(10.0)
    assert manifest.reader("seq.action_fetch_wait_ms")(run) == pytest.approx(7.0)
    assert manifest.reader("seq.update_dispatch_ms")(run) == pytest.approx(100.0)
