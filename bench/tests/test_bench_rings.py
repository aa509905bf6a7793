"""The mesh cell's per-layer readers (bench/rings.py, bench/wire_bytes.py
and the ``*.mesh`` metrics) on a small hand-made trace of four devices
in two pipeline stages."""
import pytest

from bench import harness, rings, wire_bytes

STAGE = "jit(step)/jvp(jit(body))/shard_map/while/body/stage_wire/ppermute"
TENSOR = ("jit(step)/transpose(jvp(jit(body)))/shard_map/while/body/"
          "layers/checkpoint/attn/tensor_wire/ppermute")
OP_NAMES = {"collective-permute-start.1": STAGE,
            "collective-permute-done.1": STAGE,
            "collective-permute-start.2": TENSOR,
            "collective-permute-done.2": TENSOR,
            "all-reduce.3": "jit(step)/jvp(vocab)/vocab/reduce_sum",
            "fusion.4": "jit(step)/jvp(jit(body))/layers/attn/dot_general",
            "quantize_wire.5": STAGE.replace("ppermute", "pallas_call")}
# one stage hop's payload: 8 rows of 1024 elements, a 64-byte tile meta
MESH_WIRE = {"stage": {"hops": 2, "payload_bytes_per_hop": 8 * 1024 + 64,
                       "bytes": 2 * (8 * 1024 + 64),
                       "elems_per_hop": 8 * 1024, "route": "pallas"},
             "tensor": {"hops": 4, "payload_bytes_per_hop": 4104,
                        "bytes": 4 * 4104, "elems_per_hop": 4096,
                        "route": "jnp"}}


def _device(shift):
    # window 0..100 ns: a fusion 0..40; a stage hop 40..50 with nothing
    # else running; a tensor hop 50..70, its last 10 ns under fusion 60..70;
    # the wire kernel twice, 70..74 and 74..78; an all-reduce 80..85;
    # idle 78..80 and 85..100 (and the ``shift`` ns before 40)
    return [["fusion.4", 0, 40 - shift],
            ["collective-permute-start.1", 40, 2],
            ["collective-permute-done.1", 42, 8],
            ["collective-permute-start.2", 50, 2],
            ["collective-permute-done.2", 52, 18],
            ["fusion.4", 60, 10],
            ["quantize_wire.5", 70, 4], ["quantize_wire.5", 74, 4],
            ["all-reduce.3", 80, 5]]


def _looped(devices):
    """The same ops inside a while loop's event, as the trace draws a
    loop: one op around its body's ops."""
    return {d: [["while.8", 0, 90]] + ops for d, ops in devices.items()}


def _ctx(op_names=OP_NAMES, mesh_wire=MESH_WIRE, devices=None,
         looped=False):
    devices = devices or {"0": _device(0), "1": _device(0),
                          "2": _device(10), "3": _device(10)}
    tr = {"window": [0.0, 100.0],
          "devices": _looped(devices) if looped else devices,
          "host": [], "op_names": op_names}
    ctx = harness.Ctx(cell_name="tiny-mesh", cell={}, conf_name="tiny",
                      conf={}, mix={}, seed=1, seconds=1.0, trace=True,
                      devices=[], peak={"hbm_bytes_per_s": 1e9}, t0=0.0)
    ctx.layer.update({"trace": tr, "steps": 1, "mesh_wire": mesh_wire,
                      "stage_devices": [["0", "1"], ["2", "3"]]})
    return ctx


def _read(name, ctx):
    return harness.metric_reader(name)(ctx)


@pytest.mark.parametrize("looped", [False, True])
def test_exposed_time_splits_by_ring_scope(looped):
    ctx = _ctx(looped=looped)
    # stage: 40..50, nothing else runs
    assert _read("stage_wire_exposed_ms.mesh", ctx) == pytest.approx(1e-5)
    # tensor: 50..70, 60..70 under the fusion
    assert _read("tensor_wire_exposed_ms.mesh", ctx) == pytest.approx(1e-5)
    # every collective: the two rings' 20 ns and the all-reduce's 5
    assert _read("collective_exposed_ms.mesh", ctx) == pytest.approx(2.5e-5)


def test_ring_instructions_need_the_scope_as_a_whole_component():
    names = dict(OP_NAMES, **{"collective-permute-start.9":
                              "jit(step)/my_stage_wire_x/ppermute"})
    assert rings.ring_instructions(names, "stage_wire") == {
        "collective-permute-start.1", "collective-permute-done.1"}


@pytest.mark.parametrize("looped", [False, True])
def test_stage_idle_share_is_the_larger_stage(looped):
    # stage 0 computes 0..40, 60..78: 42 ns of the 100 idle or on the
    # rings; stage 1 also 30..40
    assert _read("stage_idle_pct.mesh", _ctx(looped=looped)) == \
        pytest.approx(52.0)


def test_kernel_time_by_name():
    assert _read("wire_kernel_ms.mesh", _ctx()) == pytest.approx(8e-6)


def test_roofline_from_counted_bytes():
    # 2 calls of 4 * 8192 read + 8256 written bytes at 1 GB/s over 8 ns
    need = 2 * (4 * 8192 + 8256) / 1e9
    assert wire_bytes.kernel_calls(MESH_WIRE) == 2
    assert _read("wire_kernel_roofline.mesh", _ctx()) == pytest.approx(
        100.0 * need / 8e-9)
    # a trace with another number of kernel calls is not read
    three = dict(MESH_WIRE, stage=dict(MESH_WIRE["stage"], hops=3))
    assert _read("wire_kernel_roofline.mesh", _ctx(mesh_wire=three)) is None


@pytest.mark.parametrize("name", ["stage_wire_exposed_ms.mesh",
                                  "tensor_wire_exposed_ms.mesh"])
def test_no_reading_without_the_scopes(name):
    # the op map of a program that does not name its rings
    bare = {k: v.replace("stage_wire/", "").replace("tensor_wire/", "")
            for k, v in OP_NAMES.items()}
    assert _read(name, _ctx(op_names=bare)) is None


@pytest.mark.parametrize("name", [
    "collective_exposed_ms.mesh", "stage_wire_exposed_ms.mesh",
    "tensor_wire_exposed_ms.mesh", "stage_idle_pct.mesh",
    "wire_kernel_ms.mesh", "wire_kernel_roofline.mesh"])
def test_no_reading_without_a_trace(name):
    ctx = _ctx()
    ctx.layer.clear()
    assert _read(name, ctx) is None


def test_no_kernel_reading_without_the_kernels():
    devices = {d: [op for op in _device(0) if op[0] != "quantize_wire.5"]
               for d in "0123"}
    ctx = _ctx(devices=devices)
    assert _read("wire_kernel_ms.mesh", ctx) is None
    assert _read("wire_kernel_roofline.mesh", ctx) is None
