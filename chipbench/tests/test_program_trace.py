"""The program's spans, counters and phase scopes, read from traces built by
hand: which op counts under which phase, what the runner's own time is, and
that a program without them gives the new readers nothing to read."""
import pytest

from chipbench import harness, program_trace as pt

NEW_READERS = ("runner_gen_us_per_event", "pack_us_per_event",
               "run_self_us_per_event", "blocks_per_event",
               "grad_device_us_per_event", "mix_device_us_per_event",
               "rows_device_us_per_event", "snapshot_device_us_per_event",
               "other_device_us_per_event")
DEVICE = {"grad": "grad_device_us_per_event",
          "mix": "mix_device_us_per_event",
          "rows": "rows_device_us_per_event",
          "snapshot": "snapshot_device_us_per_event",
          "other": "other_device_us_per_event"}
BODY = "jit(block_sparse)/while/body/cond/branch_1_fun"


def _op(name, opcode="fusion"):
    return f"%{name} = f32[4]{{0}} {opcode}(f32[4]{{0}} %p), kind=kLoop"


# window 0..1000 ns.  Two block programs (100-400, 500-800) and an eval
# program (850-900).  Inside the blocks: a while and a conditional
# (containers), leaf ops under each phase, one under two phases (the inner
# wins), one under none, and one that names no scope at all.
HAND = {
    "window": [0.0, 1000.0], "devices": 1,
    "ops": [["%w = (f32[4]) while((f32[4]) %t)", 100.0, 300.0],
            ["%c = (f32[4]) conditional(s32[] %p)", 110.0, 280.0],
            [_op("g"), 110.0, 40.0],
            [_op("m"), 150.0, 30.0],
            [_op("ga"), 180.0, 20.0],
            [_op("ps"), 200.0, 10.0],
            [_op("sc"), 210.0, 15.0],
            [_op("su"), 225.0, 25.0],
            [_op("gm"), 250.0, 50.0],
            [_op("cp", "copy"), 300.0, 60.0],
            [_op("nb"), 360.0, 5.0],
            [_op("g2"), 500.0, 200.0],
            [_op("ev"), 850.0, 50.0]],
    "op_scopes": ["jit(block_sparse)/while", f"{BODY}",
                  f"{BODY}/grad/vmap(jvp())/dot_general",
                  f"{BODY}/mix/dot_general",
                  f"{BODY}/sparse_gather/gather",
                  f"{BODY}/pool_select/gather",
                  f"{BODY}/sparse_scatter/scatter",
                  f"{BODY}/s_update/select_n",
                  f"{BODY}/mix/grad/mul;grad/transpose(jvp())/mul",
                  f"{BODY}/copy",
                  "",
                  f"{BODY}/grad/dot_general",
                  "jit(eval_row)/grad/dot_general"],
    "modules": [["jit_block_sparse(1)", 100.0, 300.0],
                ["jit_block_sparse(1)", 500.0, 300.0],
                ["jit_eval_row(2)", 850.0, 50.0]],
    # a run 0-950 holding gen 10-60, pack 60-90, dispatch 90-95,
    # eval 400-420, drain 800-840 and a nested pack 60-70
    "host": [["chipbench:run", 0.0, 960.0],
             ["runner:run", 5.0, 945.0],
             ["runner:gen", 10.0, 50.0],
             ["chipbench:gen", 12.0, 45.0],
             ["runner:pack", 60.0, 30.0],
             ["runner:pack", 60.0, 10.0],
             ["dispatch:sparse_scan", 90.0, 5.0],
             ["runner:eval", 400.0, 20.0],
             ["runner:drain", 800.0, 40.0]],
    "counters": [["runner:run", 5.0, 445.0,
                  {"events": 30, "blocks": 4, "rows": 40, "active": 90,
                   "grad": 60, "restarts": 55}],
                 ["runner:run", 450.0, 500.0,
                  {"events": 10, "blocks": 1, "rows": 8, "active": 30,
                   "grad": 20, "restarts": 15}]],
}


class Rec:
    events, window_s, trace = 40, 1e-6, HAND


def _read(name, rec=Rec):
    return harness.load_module("metrics", name).reduce(rec)


def test_phase_of_takes_the_innermost_phase():
    assert pt.phase_of(f"{BODY}/grad/vmap(jvp())/dot_general") == "grad"
    assert pt.phase_of(f"{BODY}/mix/grad/mul") == "grad"
    assert pt.phase_of(f"{BODY}/sparse_gather/pool_select/x") == \
        "pool_select"
    # a fused op's joined paths: the first that names a phase decides
    assert pt.phase_of("a/copy;b/mix/mul;c/grad/mul") == "mix"
    assert pt.phase_of(f"{BODY}/copy") is None
    assert pt.phase_of("") is None
    # a phase is a whole path component, not a substring
    assert pt.phase_of(f"{BODY}/mixer/gradient") is None


def test_leaf_ops_of_blocks_by_hand():
    ops = [(text.split(" ")[0], sec, phase)
           for text, sec, phase in pt.block_leaf_ops(HAND)]
    # containers and the eval program's op are left out
    assert [o[0] for o in ops] == ["%g", "%m", "%ga", "%ps", "%sc", "%su",
                                   "%gm", "%cp", "%nb", "%g2"]
    assert [o[2] for o in ops] == ["grad", "mix", "sparse_gather",
                                   "pool_select", "sparse_scatter",
                                   "s_update", "grad", None, None, "grad"]


def test_phase_seconds_partition_the_leaf_time():
    split = pt.phase_seconds(HAND)
    assert split == {"grad": pytest.approx(290e-9),
                     "mix": pytest.approx(30e-9),
                     "rows": pytest.approx(45e-9),
                     "snapshot": pytest.approx(25e-9),
                     "other": pytest.approx(65e-9)}
    leaf = sum(sec for _, sec, _ in pt.block_leaf_ops(HAND))
    assert sum(split.values()) == pytest.approx(leaf)
    block, bare = pt.uncovered(HAND)
    assert block == pytest.approx(600e-9)
    assert bare == pytest.approx(600e-9 - 455e-9)
    assert pt.unscoped_ops(HAND)[0][0] == "%cp copy f32[4]"


def test_device_readers_by_hand():
    read = {k: _read(v) for k, v in DEVICE.items()}
    assert read == {"grad": pytest.approx(290e-9 / 40 * 1e6),
                    "mix": pytest.approx(30e-9 / 40 * 1e6),
                    "rows": pytest.approx(45e-9 / 40 * 1e6),
                    "snapshot": pytest.approx(25e-9 / 40 * 1e6),
                    "other": pytest.approx(65e-9 / 40 * 1e6)}


def test_host_readers_by_hand():
    assert _read("runner_gen_us_per_event") == pytest.approx(
        50e-9 / 40 * 1e6)
    # the nested pack counts once
    assert _read("pack_us_per_event") == pytest.approx(30e-9 / 40 * 1e6)
    # 945 of run less gen 50, pack 30, dispatch 5, eval 20, drain 40
    assert pt.run_self_seconds(HAND) == pytest.approx(800e-9)
    assert _read("run_self_us_per_event") == pytest.approx(
        800e-9 / 40 * 1e6)
    assert pt.counter_totals(HAND) == {"events": 40, "blocks": 5,
                                       "rows": 48, "active": 120,
                                       "grad": 80, "restarts": 70}
    assert _read("blocks_per_event") == pytest.approx(5 / 40)


def test_idle_by_innermost_program_span():
    idle = {k: v[0] for k, v in pt.idle_by_program_span(HAND).items()}
    # device idle 0-100, 400-500, 700-850, 900-1000; the benchmark's own
    # spans do not label it
    assert idle == {"outside run": pytest.approx(5 + 50),
                    "runner:gen": pytest.approx(50),
                    "runner:pack": pytest.approx(30),
                    "dispatch:sparse_scan": pytest.approx(5),
                    "runner:run": pytest.approx(5 + 5 + 80 + 100 + 10 + 50),
                    "runner:eval": pytest.approx(20),
                    "runner:drain": pytest.approx(40)}


def test_a_program_without_scopes_or_spans_reads_nothing():
    # the parent's blocks: only sparse_gather / sparse_scatter scopes
    old_scopes = [s.replace("grad/", "").replace("mix/", "")
                  .replace("s_update/", "") for s in HAND["op_scopes"]]
    old = dict(HAND, op_scopes=old_scopes, counters=[],
               host=[h for h in HAND["host"]
                     if not h[0].startswith("runner:")])

    class Old(Rec):
        trace = old
    assert pt.phase_seconds(old) is None
    for name in NEW_READERS:
        assert _read(name, Old) is None, name


def test_an_untraced_run_reads_nothing():
    class Untraced(Rec):
        trace = None
    for name in NEW_READERS:
        assert _read(name, Untraced) is None, name


def test_cut_keeps_what_overlaps():
    part = pt.cut(HAND, 450.0, 820.0)
    assert part["window"] == [450.0, 820.0]
    assert [o[0].split(" ")[0] for o in part["ops"]] == ["%g2"]
    assert part["op_scopes"] == [f"{BODY}/grad/dot_general"]
    assert [m[1] for m in part["modules"]] == [500.0]
    assert {h[0] for h in part["host"]} == {"chipbench:run", "runner:run",
                                            "runner:drain"}
    # the counters of the runs that overlap the cut
    assert pt.counter_totals(part)["events"] == 10
    # the kept op lies inside the cut: its time counts in full
    assert pt.phase_seconds(part)["grad"] == pytest.approx(200e-9)
