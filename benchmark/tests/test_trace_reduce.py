"""trace_reduce on a hand-made trace with known busy intervals, gaps and
annotations. Times in ns; the window is [1000, 11000) = 10 us."""
import pytest

from benchmark import trace_reduce as tr


def plane(name, **lines):
    return {"name": name,
            "lines": [{"name": k.replace("_", " "), "events": v}
                      for k, v in lines.items()]}


HOST = plane("/host:CPU", python=[
    ("bench.trace_window", 1000.0, 10000.0),
    ("bench.engine_step", 1000.0, 4000.0),          # [1000, 5000)
    ("bench.generator_wait", 5000.0, 3000.0),       # [5000, 8000)
    ("bench.engine_step", 8000.0, 3000.0),          # [8000, 11000)
    ("bench.submit", 8000.0, 500.0),                # nested in the step
    ("some_runtime_call", 0.0, 20000.0),            # not ours: ignored
])
# chip 0: a while op [2000, 6000) containing two fusions, then a copy
# [9000, 10000); an event before the window is clipped away
DEV0 = plane("/device:TPU:0", XLA_Ops=[
    ("early", 0.0, 500.0),
    ("while.1", 2000.0, 4000.0),
    ("fusion.1", 2000.0, 1000.0),
    ("fusion.2", 3500.0, 1500.0),
    ("copy.1", 9000.0, 1000.0),
], Steps=[("step", 0.0, 99999.0)])
# chip 1: busy for the whole window
DEV1 = plane("/device:TPU:1", XLA_Ops=[("fusion.1", 1000.0, 10000.0)])


def test_busy_idle_and_self_times():
    red = tr.reduce_trace([HOST, DEV0, DEV1])
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["per_device"]["/device:TPU:0"]["busy_s"] == pytest.approx(5e-6)
    assert red["per_device"]["/device:TPU:1"]["busy_s"] == pytest.approx(10e-6)
    assert red["busy_s"] == pytest.approx(7.5e-6)       # mean over chips
    assert red["idle_share"] == pytest.approx(0.5)      # worst chip
    # own time: while 4000 - 1000 - 1500 = 1500; averaged over the 2 chips
    ops = red["op_seconds"]
    assert ops["while.1"] == pytest.approx(1.5e-6 / 2)
    assert ops["fusion.2"] == pytest.approx(1.5e-6 / 2)
    assert ops["fusion.1"] == pytest.approx((1e-6 + 10e-6) / 2)
    assert ops["copy.1"] == pytest.approx(1e-6 / 2)
    assert "early" not in ops
    assert red["device_ops"][0][0] == "fusion.1"


def test_gaps_are_attributed_to_the_open_annotation():
    red = tr.reduce_trace([HOST, DEV0])
    # gaps of chip 0: [1000,2000) step, [6000,9000) wait (midpoint 7500),
    # [10000,11000) step
    assert red["idle_by_host"] == pytest.approx(
        {"bench.engine_step": 2e-6, "bench.generator_wait": 3e-6})
    assert red["idle_gaps"][0] == ["sum:bench.generator_wait",
                                   pytest.approx(3e-6)]
    assert ["bench.generator_wait", pytest.approx(3e-6)] in red["idle_gaps"]


def test_innermost_annotation_wins_and_unannotated_is_named():
    host = plane("/host:CPU", python=[("bench.engine_step", 0.0, 1000.0),
                                      ("bench.submit", 100.0, 300.0)])
    dev = plane("/device:TPU:0", XLA_Ops=[("a", 0.0, 150.0),
                                          ("b", 350.0, 50.0),
                                          ("c", 2000.0, 100.0)])
    red = tr.reduce_trace([host, dev])      # no window: the device's span
    assert red["window_s"] == pytest.approx(2100e-9)
    assert red["idle_by_host"]["bench.submit"] == pytest.approx(200e-9)
    assert red["idle_by_host"][tr.UNATTRIBUTED] == pytest.approx(1600e-9)


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_trace([HOST])
    with pytest.raises(ValueError):
        tr.reduce_trace([HOST, plane("/device:TPU:0", XLA_Ops=[])])
    with pytest.raises(ValueError):
        tr.reduce_trace([HOST, plane("/device:TPU:0", Steps=[])])


def test_hlo_names_are_cut_and_custom_calls_are_summed():
    flash = ("%jvp_attn_0_.1 = (bf16[64,4096,128]{2,1,0:T(8,128)(2,1)}, "
             "f32[64,4096,8]{2,1,0}) custom-call(bf16[64,4096,128]{2,1,0} "
             "%x), custom_call_target=\"tpu_custom_call\"")
    mm = ("%fusion.7 = bf16[2,4096]{1,0:T(2,128)} fusion(f32[8]{0} %p), "
          "kind=kOutput")
    xla = ("%custom-call.1 = bf16[8]{0} custom-call(bf16[4]{0} %a), "
           "custom_call_target=\"ConcatBitcast\"")      # not a kernel
    dev = plane("/device:TPU:0", XLA_Ops=[(flash, 0.0, 300.0),
                                          (mm, 300.0, 700.0),
                                          (xla, 1000.0, 50.0)])
    flash2 = flash.replace("attn_0_", "attn_13_")     # another layer's
    dev["lines"][0]["events"].append((flash2, 1100.0, 200.0))
    red = tr.reduce_trace([dev])
    assert red["custom_call_s"] == pytest.approx(500e-9)
    assert red["busy_s"] == pytest.approx(1250e-9)
    assert red["device_ops"][1][1] == pytest.approx(500e-9)   # merged
    assert red["device_ops"][0][0] == (
        "fusion.7 = bf16[2,4096] fusion(f32[8] %p), kind=kOutput")
    assert red["device_ops"][1][0].startswith(
        "jvp_attn_N_.1 = (bf16[64,4096,128], f32[64,4096,8]) custom-call(")
    assert len(red["device_ops"][1][0]) <= 96


def test_exposed_collectives_are_summed_on_the_worst_chip():
    ag = ("%all-gather.6 = bf16[4,4096]{1,0} all-gather(bf16[1,4096]{1,0} "
          "%x), channel_id=1")
    done = ("%collective-permute-done.8 = bf16[2,8]{1,0} "
            "collective-permute-done((bf16[2,8], bf16[2,8]) %s)")
    mm = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %all-gather.6), kind=kLoop"
    dev0 = plane("/device:TPU:0", XLA_Ops=[(ag, 0.0, 100.0),
                                           (mm, 100.0, 800.0),
                                           (done, 900.0, 100.0)])
    dev1 = plane("/device:TPU:1", XLA_Ops=[(mm, 0.0, 900.0),
                                           (done, 900.0, 100.0)])
    red = tr.reduce_trace([dev0, dev1])
    assert red["per_device"]["/device:TPU:0"]["collective_s"] \
        == pytest.approx(200e-9)
    assert red["per_device"]["/device:TPU:1"]["collective_s"] \
        == pytest.approx(100e-9)
    assert red["collective_exposed_share"] == pytest.approx(0.2)
    assert not tr.is_collective(mm)      # an operand's name is no opcode
