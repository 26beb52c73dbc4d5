"""The reduction from trace events to busy and idle time, Mosaic time
and the collectives' exposed part, on a synthetic event list."""

import pytest

from benchmark import trace_reduce as tr


def ev(name, start, dur, device="/device:TPU:0", text=None):
    return {"device": device, "name": name, "start": start, "dur": dur,
            "text": (text or name).lower()}


def test_union_merges_overlaps():
    total, merged = tr.union([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert total == 5 and merged == [[0, 3], [5, 7]]


def test_only_leaves_count():
    events = [ev("while.1", 0.0, 10.0), ev("fusion.1", 0.0, 2.0),
              ev("fusion.2", 3.0, 2.0), ev("call.3", 6.0, 3.0),
              ev("fusion.4", 6.5, 1.0)]
    names = [e["name"] for e in tr.leaves(events)]
    assert names == ["fusion.1", "fusion.2", "fusion.4"]


def test_busy_idle_mosaic_and_gaps():
    events = [
        ev("while.1", 0.0, 10.0),
        ev("fusion.1", 0.0, 4.0),
        ev("flash", 4.0, 2.0, text="flash custom-call mosaic"),
        ev("fusion.2", 7.0, 3.0),
    ]
    r = tr.reduce(events)
    assert r["window_s"] == 10.0 and r["busy_s"] == 9.0
    assert r["mosaic_s"] == 2.0 and r["mosaic_events"] == 1
    assert r["collective_s"] == 0.0
    assert r["idle_gaps"][1][1] == pytest.approx(1.0)
    assert r["device_ops"][0] == ["fusion.1", 4.0]
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.1)


def test_collective_exposed_share_and_device_average():
    events = []
    for d in ("/device:TPU:0", "/device:TPU:1"):
        events += [ev("fusion.1", 0.0, 4.0, d),
                   # 3 s of all-reduce, 1 s of it under fusion.1
                   ev("all-reduce.7", 3.0, 3.0, d),
                   ev("fusion.2", 6.0, 2.0, d)]
    # the second chip idles one second more at the end
    events.append(ev("fusion.3", 9.0, 1.0, "/device:TPU:0"))
    r = tr.reduce(events)
    assert r["devices"] == 2
    assert r["collective_s"] == pytest.approx(3.0)
    assert r["collective_exposed_s"] == pytest.approx(2.0)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx((9.0 + 8.0) / 2)


def test_nothing_to_read_is_nothing():
    assert tr.reduce([]) is None


def test_async_collectives_count_as_collective_time_only():
    events = [ev("fusion.1", 0.0, 4.0), ev("fusion.2", 6.0, 2.0)]
    events.append(dict(ev("all-reduce-start.3", 3.0, 3.0), **{"async": True}))
    r = tr.reduce(events)
    assert r["busy_s"] == pytest.approx(6.0)
    assert r["collective_s"] == pytest.approx(3.0)
    assert r["collective_exposed_s"] == pytest.approx(2.0)


def test_short_name_keeps_what_tells_ops_apart():
    name = ("%fusion.6076 = (f32[128,256]{1,0:T(8,128)S(1)}, "
            "f32[128,256,37000]{1,2,0:T(8,128)}) fusion(f32[512,37000]"
            "{0,1:T(8,128)} %gte.1), kind=kOutput, calls=%fc.6479")
    assert tr.short_name(name) == "fusion.6076 kOutput f32[128,256,37000]"
    call = ('%transpose_jvp.206 = (bf16[1024,256,64]{2,1,0}) custom-call('
            's32[2]{0} %p), custom_call_target="tpu_custom_call", x={}')
    assert tr.short_name(call) \
        == "transpose_jvp.206 tpu_custom_call bf16[1024,256,64]"
    assert tr.short_name("plain") == "plain"


def test_an_op_that_consumes_a_collective_is_not_one():
    consumer = ev("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %all-reduce.5), "
                  "kind=kLoop", 0.0, 1.0)
    own = ev("%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %p), "
             "replica_groups={}", 1.0, 1.0)
    kernel = ev('%jvp.1 = bf16[8]{0} custom-call(bf16[8]{0} %p), '
                'custom_call_target="tpu_custom_call"', 2.0, 1.0)
    assert [tr.kind_of(e) for e in (consumer, own, kernel)] \
        == ["op", "collective", "mosaic"]
