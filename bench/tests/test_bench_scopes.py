"""The engine-scope and counter readers on a hand-filled trace and answer
list whose answers are known by hand."""
import pytest

from bench import run, scopes, trace

MS = 1_000_000  # ns

# a compiled program's instructions, as ``as_text`` prints them: a loop
# and its body's fusions, each with its root's op_name
HLO = """\
ENTRY %main {
  %while.1 = (s32[]) while(s32[] %p), condition=%c, body=%b
  %fusion.2 = s32[64]{0} fusion(s32[64]{0} %x), kind=kLoop, calls=%f2, \
metadata={op_name="jit(run)/while/body/vmap(engine/pull/rank)/jit(searchsorted)/while/body/gather"}
  %fusion.3 = s32[64]{0} fusion(s32[64]{0} %y), kind=kLoop, calls=%f3, \
metadata={op_name="jit(run)/while/body/vmap(engine/pull/search)/while/body/closed_call/gather"}
  %fusion.4 = u32[64,3]{1,0} fusion(s32[64]{0} %z), kind=kLoop, calls=%f4, \
metadata={op_name="jit(run)/while/body/engine/fold/vmap()/xor"}
  %fusion.5 = s32[8]{0} fusion(s32[8]{0} %w), kind=kLoop, calls=%f5, \
metadata={op_name="jit(run)/engine/push/wire/gather"}
  %fusion.6 = s32[8]{0} fusion(s32[8]{0} %v), kind=kLoop, calls=%f6, \
metadata={op_name="jit(run)/vmap(engine/push/check)/while/body/lt"}
  ROOT %fusion.7 = s32[16]{0} fusion(s32[16]{0} %u), kind=kLoop, \
calls=%f7, metadata={op_name="jit(run)/while/body/vmap(engine/pull/wire)/gather"}
}
"""


class Traffic:
    def __init__(self, answers):
        self.answers = answers


def stats(pulled, slots=None):
    st = {"wedges_pulled": pulled, "wedges_pushed": 3}
    if slots is not None:
        st["pull_tile_slots"] = slots
    return (0, None, st)


def synthetic(monkeypatch, texts=(HLO,)):
    """Two surveys; one device: a loop 0-100 ms holding rank (30 ms, of
    which a nested search event takes 10), fold (20); then push wire 5,
    push check 5, pull wire 8 and an op no program names (2 ms)."""
    ops = [("%while.1 = (s32[]) while(s32[] %p)", 0, 100 * MS),
           ("%fusion.2 = s32[64]{0:T(1024)} fusion(s32[64] %x)",
            10 * MS, 30 * MS),
           ("%fusion.3 = s32[64]{0:T(1024)} fusion(s32[64] %y)",
            20 * MS, 10 * MS),
           ("%fusion.4 = u32[64,3]{1,0} fusion(s32[64] %z)",
            50 * MS, 20 * MS),
           ("%fusion.5 = s32[8]{0} fusion(s32[8] %w)", 110 * MS, 5 * MS),
           ("%fusion.6 = s32[8]{0} fusion(s32[8] %v)", 120 * MS, 5 * MS),
           ("%fusion.7 = s32[16]{0} fusion(s32[16] %u)", 130 * MS, 8 * MS),
           ("%copy.9 = s32[16]{0} copy(s32[16] %u)", 140 * MS, 2 * MS)]
    tr = trace.Trace(window=(0, 200 * MS), devices={0: ops})
    monkeypatch.setattr(scopes, "programs", lambda traffic: list(texts))
    return run.Run(Traffic([stats(30, 40), stats(10, 60)]), None, tr,
                   "TPU v5 lite")


def test_scope_of_takes_the_innermost_engine_scope():
    assert scopes.scope_of("jit(run)/vmap(engine/pull/rank)/jit(x)/gather") \
        == "pull/rank"
    assert scopes.scope_of("jit(run)/engine/pull/rank/engine/fold/add") \
        == "fold"
    assert scopes.scope_of("jit(run)/engine/pushy/add") is None
    assert scopes.scope_of("reduce_window_sum") is None


def test_hlo_scopes_keys_by_short_name():
    got = scopes.hlo_scopes([HLO])
    assert got["fusion.2 s32[64]"] == "pull/rank"
    assert got["fusion.7 s32[16]"] == "pull/wire"
    assert got["while.1 s32[]"] is None


def test_instr_key_reads_operands_in_either_print():
    compiled = "%fusion.2 = s32[64]{0} fusion(s32[64]{0} %x, %y.1), calls=%f"
    event = "%fusion.2 = s32[64]{0:T(1024)} fusion(s32[64]{0:T(1024)} %x, " \
        "u32[8]{0} %y.1)"
    tuple_ = "%fold.3 = (s32[1,8]{1,0}, s32[2,8]) custom-call(%a, %b), x=1"
    assert scopes.instr_key(compiled) == ("fusion.2 s32[64]", ("%x", "%y.1"))
    assert scopes.instr_key(event) == scopes.instr_key(compiled)
    assert scopes.instr_key(tuple_) == ("fold.3 s32[1,8]", ("%a", "%b"))


def test_programs_that_share_names_are_told_apart_by_operands():
    # a second program numbers alike but runs its search where the first
    # ranks, on other operands
    other = HLO.replace("engine/pull/rank", "engine/setup").replace(
        "%x)", "%x2)")
    got = scopes.hlo_scopes([HLO, other])
    assert got["fusion.2 s32[64]"] is None
    assert got["fusion.3 s32[64]"] == "pull/search"
    ev = "%fusion.2 = s32[64]{0:T(1024)} fusion(s32[64] "
    assert scopes.event_scope(got, ev + "%x)") == "pull/rank"
    assert scopes.event_scope(got, ev + "%x2)") == "setup"
    # an event that shows no operands falls back to its short name
    assert scopes.event_scope(got, "%fusion.3 = s32[64]{0} fusion()") == \
        "pull/search"
    assert scopes.event_scope(got, "%fusion.2 = s32[64]{0} fusion()") is None


def test_device_time_by_scope_uses_self_times(monkeypatch):
    r = synthetic(monkeypatch)
    secs = scopes.by_scope(r)
    assert secs == {
        "setup": 0.0, "push/wire": pytest.approx(0.005),
        "push/check": pytest.approx(0.005), "hub": 0.0,
        "pull/wire": pytest.approx(0.008),
        "pull/rank": pytest.approx(0.020),    # 30 less the nested 10
        "pull/search": pytest.approx(0.010), "fold": pytest.approx(0.020),
        # the loop's own 100 - 30 - 20 ms, and the copy
        "unscoped": pytest.approx(0.052)}
    # scoped and unscoped together are the device's busy time
    assert sum(secs.values()) == pytest.approx(trace.busy_s(r.trace))


@pytest.mark.parametrize("metric,value", [
    ("push_ms.survey", 5.0), ("pull_wire_ms.survey", 4.0),
    ("pull_rank_ms.survey", 10.0), ("pull_search_ms.survey", 5.0)])
def test_scope_readers_give_ms_per_survey(monkeypatch, metric, value):
    assert run.reader(metric)(synthetic(monkeypatch)) == pytest.approx(value)


@pytest.mark.parametrize("metric", [
    "push_ms.survey", "pull_wire_ms.survey", "pull_rank_ms.survey",
    "pull_search_ms.survey"])
def test_scope_readers_read_nothing_from_a_program_without_scopes(
        monkeypatch, metric):
    plain = HLO.replace("engine/", "")
    assert run.reader(metric)(synthetic(monkeypatch, (plain,))) is None


def test_scope_readers_read_nothing_without_a_device(monkeypatch):
    r = synthetic(monkeypatch)
    r.trace.devices = {}
    assert run.reader("pull_rank_ms.survey")(r) is None


def counted(answers, devices=True):
    tr = trace.Trace(window=(0, MS), devices={0: [("fusion.1", 0, MS)]}
                     if devices else {})
    return run.Run(Traffic(answers), None, tr, "TPU v5 lite")


def test_tile_occupancy_is_pulled_over_staged_slots():
    read = run.reader("pull_tile_occupancy.survey")
    assert read(counted([stats(30, 40), stats(10, 60)])) == \
        pytest.approx(40.0)


def test_tile_occupancy_reads_nothing_without_the_counter_or_a_device():
    read = run.reader("pull_tile_occupancy.survey")
    assert read(counted([stats(30), stats(10)])) is None
    assert read(counted([])) is None
    assert read(counted([stats(30, 40)], devices=False)) is None
