"""paddle_tpu/obs/xplane.py on hand-made (start_ns, duration_ns, name)
lists: the busy union, self time, a thread's innermost spans, and idle
gaps attributed to the host span open during them."""

import pytest

from paddle_tpu.obs import xplane

MS = 1_000_000


def test_busy_union_and_self_time():
    ops = [(0, 4 * MS, "fusion"), (1 * MS, 2 * MS, "kernel"),
           (6 * MS, 2 * MS, "copy"), (7 * MS, 2 * MS, "fusion")]
    assert xplane.busy_intervals(ops) == [(0, 4 * MS), (6 * MS, 9 * MS)]
    by = xplane.self_time_by_name(ops)
    assert by["fusion"] == 2 * MS + 2 * MS      # 4 - child 2, + 2
    assert by["kernel"] == 2 * MS


def test_innermost_segments_give_a_parent_only_what_no_child_covers():
    spans = [(0, 10 * MS, "serving/step"), (1 * MS, 2 * MS, "serving/admit"),
             (4 * MS, 5 * MS, "serving/decode_step"),
             (4 * MS, 1 * MS, "serving/dispatch"),
             (5 * MS, 4 * MS, "serving/sync")]
    segs = xplane.innermost_segments(spans)
    assert segs == [(0, 1 * MS, "serving/step"),
                    (1 * MS, 3 * MS, "serving/admit"),
                    (3 * MS, 4 * MS, "serving/step"),
                    (4 * MS, 5 * MS, "serving/dispatch"),
                    (5 * MS, 9 * MS, "serving/sync"),
                    (9 * MS, 10 * MS, "serving/step")]
    total = sum(e - s for s, e, _ in segs)
    assert total == 10 * MS                     # disjoint, nothing lost


def test_a_gap_inside_one_host_span():
    ops = [(0, 2 * MS, "a"), (5 * MS, 2 * MS, "b")]         # gap [2, 5]
    host = {"loop": [(1 * MS, 5 * MS, "serving/commit")]}   # [1, 6]
    got = xplane.attribute_gaps(ops, host)
    assert got["n_gaps"] == 1 and got["gap_ns"] == 3 * MS
    assert got["by_span"] == {"serving/commit": 3 * MS}
    assert got["unattributed_ns"] == 0


def test_a_gap_straddling_two_spans_is_split_at_their_boundary():
    ops = [(0, 2 * MS, "a"), (8 * MS, 2 * MS, "b")]         # gap [2, 8]
    host = {"loop": [(0, 4 * MS, "serving/commit"),         # [0, 4]
                     (5 * MS, 5 * MS, "serving/admit")]}    # [5, 10]
    got = xplane.attribute_gaps(ops, host)
    assert got["by_span"] == {"serving/commit": 2 * MS,
                              "serving/admit": 3 * MS}
    assert got["unattributed_ns"] == 1 * MS                 # [4, 5]
    assert got["longest"][0]["by_span"] == {
        "serving/commit": 2 * MS, "serving/admit": 3 * MS,
        xplane.UNATTRIBUTED: 1 * MS}


def test_a_gap_under_no_span_is_unattributed_not_spread():
    ops = [(0, 2 * MS, "a"), (4 * MS, 1 * MS, "b"), (9 * MS, 1 * MS, "c")]
    host = {"loop": [(2 * MS, 2 * MS, "serving/plan")]}     # covers gap 1
    got = xplane.attribute_gaps(ops, host)
    assert got["n_gaps"] == 2 and got["gap_ns"] == 6 * MS
    assert got["by_span"] == {"serving/plan": 2 * MS}
    assert got["unattributed_ns"] == 4 * MS


def test_a_nested_span_takes_the_gap_from_its_parent():
    ops = [(0, 1 * MS, "a"), (7 * MS, 1 * MS, "b")]         # gap [1, 7]
    host = {"loop": [(0, 8 * MS, "serving/step"),
                     (2 * MS, 3 * MS, "serving/commit")]}   # [2, 5]
    got = xplane.attribute_gaps(ops, host)
    assert got["by_span"] == {"serving/step": 3 * MS,
                              "serving/commit": 3 * MS}
    assert got["unattributed_ns"] == 0


def test_two_host_threads_overlap_each_other_never_unattributed():
    ops = [(0, 1 * MS, "a"), (5 * MS, 1 * MS, "b")]         # gap [1, 5]
    host = {"loop": [(1 * MS, 3 * MS, "train/data_wait")],  # [1, 4]
            "feed": [(2 * MS, 2 * MS, "train/h2d")]}        # [2, 4]
    got = xplane.attribute_gaps(ops, host)
    assert got["by_span"] == {"train/data_wait": 3 * MS,
                              "train/h2d": 2 * MS}
    assert got["unattributed_ns"] == 1 * MS                 # [4, 5]


@pytest.mark.parametrize("thread", ["engine", "loadgen"])
def test_a_gap_under_a_collection_is_the_collections(thread):
    """A collection holds the interpreter whichever thread runs it: the
    gap's time under ``host/gc`` is the collection's, ahead of the engine
    thread's open ``serving/commit``; the rest stays the commit's."""
    ops = [(0, 2 * MS, "a"), (10 * MS, 1 * MS, "b")]       # gap [2, 10]
    host = {"engine": [(1 * MS, 9 * MS, "serving/commit")],  # [1, 10]
            "loadgen": [(0, 1 * MS, "serving/idle")]}
    host[thread] = host[thread] + [(4 * MS, 3 * MS, xplane.GC_SPAN)]
    got = xplane.attribute_gaps(ops, host)
    assert got["by_span"] == {xplane.GC_SPAN: 3 * MS,
                              "serving/commit": 5 * MS}
    assert got["unattributed_ns"] == 0
    assert got["longest"][0]["by_span"] == got["by_span"]


def test_a_collection_past_the_gaps_edges_and_under_no_other_span():
    ops = [(0, 2 * MS, "a"), (5 * MS, 1 * MS, "b"), (9 * MS, 1 * MS, "c")]
    host = {"loadgen": [(1 * MS, 2 * MS, xplane.GC_SPAN),   # [1, 3]
                        (8 * MS, 4 * MS, xplane.GC_SPAN)]}  # [8, 12]
    got = xplane.attribute_gaps(ops, host)
    assert got["by_span"] == {xplane.GC_SPAN: 2 * MS}       # [2,3] [8,9]
    assert got["unattributed_ns"] == 4 * MS                 # [3,5] [6,8]


def test_two_devices_each_against_the_same_host_spans():
    host = {"loop": [(0, 10 * MS, "train_step")]}
    dev0 = [(0, 4 * MS, "fusion"), (6 * MS, 4 * MS, "fusion")]
    dev1 = [(0, 9 * MS, "fusion"), (9 * MS, 1 * MS, "all-reduce")]
    r0 = xplane.device_report(dev0, [(s, d, "loss_and_grad")
                                     for s, d, _ in dev0], host)
    r1 = xplane.device_report(dev1, [(s, d, "optimizer")
                                     for s, d, _ in dev1], host)
    assert r0["busy_s"] == pytest.approx(0.008)
    assert r0["idle_share"] == pytest.approx(0.2)
    assert r0["idle"]["by_span"] == [["train_step", pytest.approx(0.002)]]
    assert r0["by_scope"] == [["loss_and_grad", pytest.approx(0.008)]]
    assert r1["idle_s"] == 0 and r1["idle"]["n_gaps"] == 0
    assert r1["by_op"][0] == ["fusion", pytest.approx(0.009)]


@pytest.mark.parametrize("line,want", [
    ("%fusion.7.remat = bf16[4]{0} fusion(bf16[4]{0} %p.1), kind=kLoop",
     "fusion:fusion.remat"),
    ('%jvp_flash_fwd_.1 = (bf16[8,64]{1,0}) custom-call(%a), '
     'custom_call_target="tpu_custom_call"', "tpu_custom_call:jvp_flash_fwd_"),
    ("%copy.594 = bf16[2]{0} copy(bf16[2]{0} %v_pool.1)", "copy:copy"),
    ("no_equals_sign.3", "no_equals_sign"),
])
def test_short_op_name(line, want):
    assert xplane.short_op_name(line) == want


@pytest.mark.parametrize("path,want", [
    ("jit(_step_impl)/jit(main)/paged_attn/dot_general", "paged_attn"),
    ("jit(step)/jit(main)/loss_and_grad/transpose(jvp(fc))/mul",
     "loss_and_grad"),
    ("jit(_step_impl)/jit(main)/dot_general", xplane.NO_SCOPE),
    ("jit(sharded)/jit(main)/shard_map/optimizer/sub", "optimizer"),
    (None, xplane.NO_SCOPE),
])
def test_scope_of(path, want):
    assert xplane.scope_of(path) == want


def test_report_of_a_directory_without_a_trace_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        xplane.report(str(tmp_path))


def test_clock_offset_bounds_hold_the_device_to_causality():
    """A synchronous loop: launch [0,2] -> the step runs [1,9] -> the wait
    ends at 10; the next launch at 12. A device clock 5 ms ahead shows the
    step at [6,14]: it 'ends' 4 ms after the host saw its result."""
    launches = [(0, 2 * MS, "serving/dispatch"),
                (12 * MS, 2 * MS, "serving/dispatch")]
    waits = [(2 * MS, 8 * MS, "serving/sync"),
             (14 * MS, 8 * MS, "serving/sync")]
    true = [(1 * MS, 8 * MS, "jit__step_impl"),
            (13 * MS, 8 * MS, "jit__step_impl")]
    lo, hi = xplane.clock_offset_bounds(true, launches, waits)
    assert (lo, hi) == (-1 * MS, 1 * MS)        # the clocks agree
    ahead = [(s + 5 * MS, d, n) for s, d, n in true]
    lo, hi = xplane.clock_offset_bounds(ahead, launches, waits)
    assert (lo, hi) == (4 * MS, 6 * MS)         # 5 ms, to within 1
    assert xplane.clock_offset_bounds(true, [], waits) is None
    assert xplane.clock_offset_bounds([], launches, waits) is None


@pytest.mark.parametrize("offset_ms", [0, 3, -2])
def test_clock_offset_bounds_with_a_step_in_flight(offset_ms):
    """The loop keeps a step in flight (serving/engine.py ``_loop``): the
    device runs step k over [10k, 10k + 10] back to back; a turn launches
    step k+1 at 10k + 1.5 and then waits for step k until 10k + 11, so the
    wait for k ends AFTER the launch of k+1 has started, and the launch
    nearest to an execution's start is the next step's. The k-th
    execution is held between the k-th launch's start and the k-th wait's
    end, whatever the trace's edges cut off."""
    launches = [(int((10 * k - 8.5) * MS), 2 * MS, "serving/dispatch")
                for k in range(1, 8)]                   # steps 1..7
    waits = [(int((10 * k + 3.5) * MS), int(7.5 * MS), "serving/sync")
             for k in range(0, 7)]                      # steps 0..6
    for k in range(1, 7):   # sync k ends after dispatch k+1 starts
        assert waits[k][0] + waits[k][1] > launches[k][0]
    shift = offset_ms * MS
    mods = [(10 * k * MS + shift, 10 * MS, "jit__step_impl")
            for k in range(1, 5)]                       # steps 1..4
    lo, hi = xplane.clock_offset_bounds(mods, launches, waits)
    assert (lo, hi) == (shift - 1 * MS, shift + int(8.5 * MS))
    assert lo <= shift <= hi
    # the nearest launch is the next step's: it would put the device
    # 1.5 ms behind a host that launched the step 8.5 ms before
    nearest = min(s - min((l for l, _, _ in launches),
                          key=lambda l: abs(l - s)) for s, _, _ in mods)
    assert nearest == shift - int(1.5 * MS)


def test_clock_offset_bounds_say_nothing_where_no_alignment_is_causal():
    """The first execution's launch cut off at the head of the trace: no
    one-to-one alignment keeps every execution behind its launch, and the
    nearest launch to the first execution is the second's. Nothing is
    held, where a guess would move the device by a whole step."""
    launches = [(12 * MS, 2 * MS, "serving/dispatch"),
                (24 * MS, 2 * MS, "serving/dispatch")]
    waits = [(2 * MS, 8 * MS, "serving/sync"),
             (14 * MS, 8 * MS, "serving/sync")]
    mods = [(1 * MS, 8 * MS, "jit__step_impl"),
            (13 * MS, 8 * MS, "jit__step_impl")]
    assert xplane.clock_offset_bounds(mods, launches, waits) is None
    # with the launch there, the same executions are held as ever
    assert xplane.clock_offset_bounds(
        mods, [(0, 2 * MS, "serving/dispatch")] + launches, waits) == (
            -1 * MS, 1 * MS)


def test_scopes_from_hlo_text():
    text = """
HloModule jit__step_impl
  %fusion.7 = bf16[4]{0} fusion(%p.1), kind=kLoop, calls=%fc.7, metadata={op_name="jit(_step_impl)/jit(main)/ffn/dot_general" source_file="x.py"}
  %paged_window_attention.24 = bf16[2]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step_impl)/jit(main)/paged_attn/paged_window_attention"}
  ROOT %copy.3 = bf16[4]{0} copy(%fusion.7), metadata={op_name="jit(_step_impl)/jit(main)/kv_write/scatter"}
  %constant.1 = s32[] constant(0)
"""
    assert xplane.scopes_from_hlo(text) == {
        "fusion.7": "ffn", "paged_window_attention.24": "paged_attn",
        "copy.3": "kv_write", "constant.1": xplane.NO_SCOPE}


@pytest.mark.parametrize("line,scopes,want", [
    ("%fusion.7 = bf16[4]{0} fusion(bf16[4]{0} %p.1), kind=kLoop",
     {"fusion.7": "ffn"}, "ffn"),
    ("%copy.3 = bf16[4]{0} copy(bf16[4]{0} %fusion.7)",
     {"copy.3": xplane.NO_SCOPE}, xplane.NO_SCOPE),
    # the text of another program than the traced one shows as such
    ("%fusion.9 = bf16[4]{0} fusion(bf16[4]{0} %p.1), kind=kLoop",
     {"fusion.7": "ffn"}, xplane.NOT_IN_TEXT),
    ("%fusion.7 = bf16[4]{0} fusion(bf16[4]{0} %p.1), kind=kLoop",
     None, xplane.NO_SCOPE),
])
def test_event_scope(line, scopes, want):
    assert xplane.event_scope(line, scopes) == want
