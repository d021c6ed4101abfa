"""Sharding-rule unit tests (no multi-device runtime needed).

``fit_spec``/``param_spec_for`` are pure given a mesh-shaped object, so a
FakeMesh with (axis_names, devices.shape) exercises the divisibility and
FSDP logic without 256 devices. The HLO collective/metric parsers are
tested on synthetic HLO text.
"""
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.launch.lowering import collective_bytes, hlo_metrics
from repro.launch.mesh import rules_for_cell


class FakeMesh:
    def __init__(self, shape, names):
        self.axis_names = tuple(names)
        self.devices = np.empty(shape)


MESH = FakeMesh((16, 16), ("data", "model"))
PODMESH = FakeMesh((2, 16, 16), ("pod", "data", "model"))


def _spec(path_keys, shape, rules):
    class K:
        def __init__(self, k):
            self.key = k

    return sharding.param_spec_for([K(k) for k in path_keys], shape, rules)


def test_param_specs_tensor_parallel():
    r = sharding.DEFAULT_RULES.with_mesh(MESH)
    assert _spec(["attn", "wq"], (4096, 32, 128), r) == P(None, "model", None)
    assert _spec(["attn", "wo"], (32, 128, 4096), r) == P("model", None, None)
    assert _spec(["mlp", "w_gate"], (4096, 14336), r) == P(None, "model")
    assert _spec(["mlp", "w_down"], (14336, 4096), r) == P("model", None)
    assert _spec(["embed_table"], (49152, 4096), r) == P("model", None)
    # norms replicate
    assert _spec(["ln1"], (4096,), r) == P(None)


def test_param_specs_divisibility_fallback():
    r = sharding.DEFAULT_RULES.with_mesh(MESH)
    # smollm: 15 heads, 5 kv heads — not divisible by 16 => replicated
    assert _spec(["attn", "wq"], (960, 15, 64), r) == P(None, None, None)
    assert _spec(["attn", "wk"], (960, 5, 64), r) == P(None, None, None)
    # odd vocab (granite-moe) => replicated embed
    assert _spec(["embed_table"], (49155, 1024), r) == P(None, None)


def test_param_specs_experts():
    r = sharding.DEFAULT_RULES.with_mesh(MESH)
    assert _spec(["moe", "experts", "w_gate"], (64, 2048, 1408), r) \
        == P("model", None, None)
    # scan-stacked experts: extra leading dim
    assert _spec(["moe", "experts", "w_gate"], (13, 64, 2048, 1408), r) \
        == P(None, "model", None, None)


def test_param_specs_fsdp_shards_largest_free_dim():
    r = sharding.DEFAULT_RULES.with_mesh(MESH).with_fsdp(True)
    # wq (4096, 32, 128): heads sharded by TP; FSDP takes dim0 over data
    s = _spec(["attn", "wq"], (4096, 32, 128), r)
    assert s == P(("pod", "data"), "model", None) or \
        s == P("data", "model", None)
    # small leaves stay replicated
    assert _spec(["ln1"], (4096,), r) == P(None)


def test_fit_spec_drops_nondivisible():
    got = sharding.fit_spec(P("model", "data"), (15, 32), MESH)
    assert got == P(None, "data")
    got = sharding.fit_spec(P(("pod", "data"),), (48,), PODMESH)
    assert got == P(None)  # 48 % 32 != 0
    got = sharding.fit_spec(P(("pod", "data"),), (64,), PODMESH)
    assert got == P(("pod", "data"))


def test_with_mesh_drops_unknown_axes_and_normalises_1tuples():
    """``Rules.with_mesh``: rules referencing axes the mesh lacks are
    dropped; a multi-axis rule that survives with ONE axis resolves to
    the same PartitionSpec as the bare name."""
    r = sharding.Rules({
        "batch": ("pod", "data"),   # pod missing -> ("data",)
        "heads": "model",           # plain string kept verbatim
        "mlp": "tensor",            # unknown string -> dropped to None
        "experts": ("ep", "tp"),    # both unknown -> None
        "seq": None,                # None passes through
        "state": ("data", "model"),  # both valid -> tuple preserved
    }).with_mesh(MESH)
    assert r.mapping["batch"] == ("data",)
    assert r.mapping["heads"] == "model"
    assert r.mapping["mlp"] is None
    assert r.mapping["experts"] is None
    assert r.mapping["seq"] is None
    assert r.mapping["state"] == ("data", "model")
    assert r.spec("batch") == P("data")
    # original Rules object untouched (with_mesh is functional)
    assert r.mesh is MESH


def test_with_mesh_of_inference_mesh_axes():
    """DEFAULT_RULES against the inference chains x data mesh: every
    surviving value resolves to the data axis or to None."""
    m = FakeMesh((2, 4), ("chains", "data"))
    r = sharding.DEFAULT_RULES.with_mesh(m)
    for k, v in r.mapping.items():
        assert v is None or P(v) == P("data"), (k, v)
    assert r.spec("batch") == P("data")  # ("pod","data") -> data


def test_rules_for_cell_fsdp_threshold():
    small = rules_for_cell("train", n_params=4e8, model_axis=16)
    big = rules_for_cell("train", n_params=27e9, model_axis=16)
    assert not small.fsdp
    assert big.fsdp
    long_r = rules_for_cell("long")
    assert long_r.mapping["batch"] is None
    assert long_r.mapping["kv_seq"] == ("pod", "data")


# ---------------------------------------------------------------------------
# HLO parsers
# ---------------------------------------------------------------------------
_HLO = """
HloModule jit_step

%fused_computation.1 (p0: f32[128,256]) -> f32[128,256] {
  %p0 = f32[128,256]{1,0} parameter(0)
  %big = f32[1024,1024]{1,0} exponential(%p0)
  ROOT %r = f32[128,256]{1,0} negate(%p0)
}

ENTRY %main (a: bf16[1024,512], b: bf16[512,256]) -> f32[1024,256] {
  %a = bf16[1024,512]{1,0} parameter(0)
  %b = bf16[512,256]{1,0} parameter(1)
  %dot.1 = f32[1024,256]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[1024,256]{1,0} all-reduce(%dot.1), replica_groups={}
  %ag = bf16[2048,512]{1,0} all-gather(%a), dimensions={0}
  %tup = (f32[64]{0}, f32[64]{0}) all-to-all(%dot.1, %dot.1)
  ROOT %out = f32[1024,256]{1,0} add(%ar, %ar)
}
"""


def test_collective_bytes_parser():
    got = collective_bytes(_HLO)
    assert got["all-reduce"] == 1024 * 256 * 4
    assert got["all-gather"] == 2048 * 512 * 2
    assert got["all-to-all"] == 2 * 64 * 4


def test_hlo_metrics_dot_flops_and_traffic():
    m = hlo_metrics(_HLO)
    assert m["dot_flops"] == 2 * 1024 * 256 * 512
    # entry traffic: params + dot + ar + ag + tup + out, x2; the
    # fusion-internal %big (register-resident) must NOT count
    per_op = (1024 * 512 * 2 + 512 * 256 * 2 + 1024 * 256 * 4 * 3
              + 2048 * 512 * 2 + 2 * 64 * 4)
    assert m["traffic_bytes"] == 2 * per_op
