"""CPU tests of the benchmark's yardstick: intervals, trace reduction,
work counts and discovery. Nothing here describes a chip topology."""

import math

import pytest

from bench import check, discover, intervals, work, xplane


def test_union_merges_overlaps_and_drops_empty():
    assert intervals.union([(3, 4), (0, 1), (0.5, 2), (5, 5)]) == [
        (0, 2), (3, 4)]
    assert intervals.length([(0, 1), (0.5, 2), (3, 4)]) == 3


def test_subtract_clip_and_gaps():
    assert intervals.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert intervals.clip([(0, 4), (6, 12)], 2, 8) == [(2, 4), (6, 8)]
    assert intervals.gaps([(1, 2), (4, 9)], 0, 10) == [
        (0, 1), (2, 4), (9, 10)]


def _ev(name, start, end, module="jit_step", opcode=""):
    return xplane.Op(name=name, start=start, end=end, module=module,
                     opcode=opcode)


def _trace():
    # one device: two steps in a 10 s window, with a collective that
    # overlaps compute for 1 s and runs alone for 1 s (named as the TPU
    # trace names the pencil's legs)
    ops = [_ev("fusion.1", 1.0, 3.0),
           _ev("all_to_all.2", 2.0, 4.0, opcode="all-to-all"),
           _ev("fusion.3", 4.0, 5.0), _ev("copy.4", 7.0, 8.0),
           _ev("fusion.1", 8.0, 9.0, module="jit_other")]
    spans = [xplane.Span("bench.window", 0.0, 10.0),
             xplane.Span("execute_async", 5.0, 6.5),
             xplane.Span("realize", 6.5, 7.0)]
    return xplane.Trace(devices={0: ops}, spans=spans, window=(0.0, 10.0))


def test_busy_idle_and_exposed_collective():
    tr = _trace()
    assert tr.busy_s(0) == pytest.approx(6.0)
    assert tr.idle_pct() == pytest.approx(40.0)
    steps = tr.module_ops("jit_step")
    assert tr.op_seconds(steps) == pytest.approx(5.0)
    # collective alone from 3 to 4 s of the step's 5 busy seconds
    assert tr.exposed_collective_pct("jit_step") == pytest.approx(20.0)


@pytest.mark.parametrize("text,code,collective", [
    ("%all_to_all.48 = f32[1024,512,1024]{2,1,0:T(8,128)} all-to-all("
     "f32[1024,512,1024]{2,1,0:T(8,128)} %copy.3), channel_id=5, "
     "replica_groups={{0,1},{2,3}}, dimensions={1}", "all-to-all", True),
    ("%all-reduce-start.1 = f32[2]{0} all-reduce-start(f32[2]{0} %p), "
     "to_apply=%add", "all-reduce-start", True),
    ("%collective-permute-done = f32[8]{0} collective-permute-done("
     "f32[8]{0} %s)", "collective-permute-done", True),
    ("%matfft_1024.1 = (f32[262144,1024]{1,0:T(8,128)}, f32[262144,1024]"
     "{1,0:T(8,128)}) custom-call(f32[262144,1024]{1,0:T(8,128)} %a)",
     "custom-call", False),
    ("%fusion.7 = f32[4]{0:T(256)} fusion(f32[4]{0} %x), kind=kLoop",
     "fusion", False),
    ("all_to_all.48", "", True),
    ("all-gather.3", "", True),
    ("copy.4", "", False),
])
def test_collectives_are_found_by_opcode_or_either_spelling(
        text, code, collective):
    assert xplane.opcode(text) == code
    op = _ev(xplane._op_name(text), 0.0, 1.0, opcode=xplane.opcode(text))
    assert op.collective is collective


def test_gap_attribution_names_the_host_span():
    gaps = _trace().idle_gaps(top=10)
    assert gaps[0] == ["execute_async", pytest.approx(1.5)]
    names = [g[0] for g in gaps]
    assert "realize" in names and "bench.window" in names


def test_gaps_are_named_by_the_innermost_span_of_any_thread():
    """Benchmark and program spans of several threads overlap; each idle
    piece takes the name of the shortest span that covers it, as a scan
    of every span at every piece finds it."""
    import random
    rnd = random.Random(3)
    ops = []
    t = 0.0
    for i in range(200):
        t += rnd.uniform(0.001, 0.05)
        ops.append(_ev(f"fusion.{i}", t, t + rnd.uniform(0.001, 0.05)))
    spans = [xplane.Span("bench.window", 0.0, 10.0)]
    for i in range(300):
        s = rnd.uniform(0.0, 10.0)
        name = rnd.choice(["fft.stream.read", "fft.stream.write",
                           "fft.stream.wait_inflight", "bench.wait"])
        spans.append(xplane.Span(name, s, s + rnd.uniform(0.0005, 2.0)))
    tr = xplane.Trace(devices={0: ops}, spans=spans, window=(0.0, 10.0))

    def brute():
        pieces = []
        for a, b in intervals.gaps(tr._clip(ops), 0.0, 10.0):
            cuts = sorted({a, b, *(x for s in spans for x in (s.start, s.end)
                                   if a < x < b)})
            for lo, hi in zip(cuts, cuts[1:]):
                mid = (lo + hi) / 2
                inside = [s for s in spans if s.start <= mid < s.end]
                name = min(inside, key=lambda s: s.end - s.start).name
                if pieces and pieces[-1][0] == name and pieces[-1][2] == lo:
                    pieces[-1][1] += hi - lo
                    pieces[-1][2] = hi
                else:
                    pieces.append([name, hi - lo, hi])
        pieces.sort(key=lambda p: -p[1])
        return [[n, s] for n, s, _ in pieces[:10]]

    got = tr.idle_gaps(top=10)
    assert got == brute()
    assert {n for n, _ in got} & {"fft.stream.read", "fft.stream.write",
                                  "fft.stream.wait_inflight"}


def test_device_op_breakdown_is_sorted_by_time():
    ops = _trace().device_ops(top=10)
    assert ops[0] == ["jit_step:fusion.1", pytest.approx(2.0)]
    assert [o[1] for o in ops] == sorted((o[1] for o in ops), reverse=True)


def test_c2c_work_counts():
    ops, nbytes = work.c2c_work((1024,), 262144)
    assert ops == 5 * 1024 * 10 * 262144
    assert nbytes == 16 * 1024 * 262144
    ops3, bytes3 = work.c2c_work((1024, 1024, 2048))
    n = 1024 * 1024 * 2048
    assert ops3 == 5 * n * 31 and bytes3 == 16 * n * 3


def test_least_seconds_picks_the_binding_bound():
    t, bound = work.least_seconds(*work.c2c_work((1024,), 262144),
                                  "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(16 * 1024 * 262144 / 819e9)
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_every_cell_is_found_by_name():
    bench = discover.with_staged(discover.load_benchmark())
    names = {c["name"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert cell["config"] in names
        assert discover.find_cell(bench, cell["name"]) is cell
        cfg = discover.load_config(cell["config"])
        traffic = discover.load_traffic(cell["traffic"])
        assert callable(discover.load_generator(traffic["generator"]).run)
        assert discover.load_reference(cell["config"]).__doc__
        assert callable(discover.load_generator(traffic["generator"]).control)
        assert cfg["chips"] == cell["chips"]
        reported = {m["name"] for m in discover.end_to_end_for(
            bench, cell["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        layer = discover.per_layer_for(bench, cell["name"])
        assert layer and all(m["moves"] in reported for m in layer)
    for m in bench["per_layer"]:
        assert callable(discover.load_reader(m["name"]))
    with pytest.raises(KeyError):
        discover.find_cell(bench, "no.such_cell")


def test_a_reader_is_found_by_its_stem(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "dev.idle.py").write_text(
        "def read(ctx):\n    return 'stem'\n")
    (tmp_path / "metrics" / "dev.idle.ft.py").write_text(
        "def read(ctx):\n    return 'own'\n")
    assert discover.load_reader("dev.idle.batch", tmp_path)(None) == "stem"
    assert discover.load_reader("dev.idle.ft", tmp_path)(None) == "own"
    with pytest.raises(FileNotFoundError):
        discover.load_reader("other.idle.batch", tmp_path)


def test_config_files_match_benchmark_entries():
    bench = discover.load_benchmark()
    assert {"paper_capture_c2c1024", "npb_ft_classD"} <= {
        c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = discover.load_config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert cfg["reduced"] == c["reduced"]
        assert all(lim is not None and math.isfinite(lim) and lim > 0
                   for lim in cfg["check"].values())



@pytest.mark.parametrize("value,limit,ok", [
    (1e-7, 1e-6, True), (1e-6, 1e-6, True), (2e-6, 1e-6, False),
    (float("nan"), 1e-6, False), (float("inf"), 1e-6, False),
    (1e-7, None, False)])
def test_verdict_holds_each_number_to_its_limit(value, limit, ok):
    assert check.verdict([("max_rel_l2", value, limit)]) is ok
    assert check.report([("max_rel_l2", value, limit)]) == {
        "max_rel_l2": {"value": value, "limit": limit}}
