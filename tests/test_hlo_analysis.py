"""The trip-count-corrected HLO analyzer that §Roofline depends on, plus
the collective-contract primitives behind the multi-host dryrun gate."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import (
    analyze_hlo,
    check_collective_contract,
    collective_ops,
)


def test_scan_trip_count_correction():
    def f(xs, w):
        def body(c, x):
            return c @ w + x @ w, ()
        c, _ = jax.lax.scan(body, xs[0], xs)
        return c.sum()

    xs = jnp.zeros((7, 32, 64))
    w = jnp.zeros((64, 64))
    compiled = jax.jit(f).lower(xs, w).compile()
    res = analyze_hlo(compiled.as_text())
    expected = 7 * 2 * (2 * 32 * 64 * 64)  # 7 iterations x 2 matmuls
    assert abs(res["flops"] - expected) / expected < 0.02
    # raw XLA undercounts by ~the trip count
    raw = compiled.cost_analysis()
    assert res["flops"] > 5 * raw["flops"]


def test_nested_scan():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, ()
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, ()
        c, _ = jax.lax.scan(outer, x, None, length=5)
        return c.sum()

    x = jnp.zeros((16, 16))
    w = jnp.zeros((16, 16))
    compiled = jax.jit(f).lower(x, w).compile()
    res = analyze_hlo(compiled.as_text())
    expected = 5 * 3 * 2 * 16 * 16 * 16
    assert abs(res["flops"] - expected) / expected < 0.05


def test_plain_matmul_exact():
    compiled = jax.jit(
        lambda a, b: a @ b).lower(jnp.zeros((128, 256)),
                                  jnp.zeros((256, 64))).compile()
    res = analyze_hlo(compiled.as_text())
    assert res["flops"] == 2 * 128 * 256 * 64
    assert res["bytes"] >= (128 * 256 + 256 * 64 + 128 * 64) * 4


# -- collective-contract primitives (the dryrun --gate building blocks) -------

# Hand-written optimized-HLO shapes: an add-all-reduce over iota groups of
# 2, a max-all-reduce over explicit groups of 4, and an all-gather over
# iota groups of 16.
_SYNTH = """\
HloModule synthetic

%sum (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.1 = f32[] add(f32[] %x, f32[] %y)
}

%maxer (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %maximum.1 = f32[] maximum(f32[] %x, f32[] %y)
}

ENTRY %main (p0: f32[16]) -> f32[128,32] {
  %p0 = f32[16]{0} parameter(0)
  %ar0 = f32[16]{0} all-reduce(f32[16]{0} %p0), replica_groups=[4,2]<=[8], to_apply=%sum
  %ar1 = f32[16]{0} all-reduce(f32[16]{0} %ar0), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%maxer
  %shard = f32[8,32]{1,0} broadcast(f32[16]{0} %ar1), dimensions={0}
  ROOT %ag = f32[128,32]{1,0} all-gather(f32[8,32]{1,0} %shard), replica_groups=[2,16]<=[32], dimensions={0}
}
"""  # noqa: E501


def test_collective_ops_inventory():
    ops = collective_ops(_SYNTH)
    assert [(c["op"], c["group_size"], c["dims"], c["reduce"])
            for c in ops] == [
        ("all-reduce", 2, [16], "add"),
        ("all-reduce", 4, [16], "max"),
        ("all-gather", 16, [128, 32], ""),
    ]
    assert all(c["dtype"] == "f32" for c in ops)
    assert ops[2]["bytes"] == 128 * 32 * 4


def test_contract_holds_on_matching_hlo():
    contract = [
        {"op": "all-reduce", "group_size": 2, "dims": [16], "dtype": "f32",
         "reduce": "add"},
        {"op": "all-reduce", "group_size": 4, "reduce": "max"},
        {"op": "all-gather", "group_size": 16, "dims": [128, 32]},
        # wildcard row: any two all-reduces, shapes/groups unconstrained
        {"op": "all-reduce", "min_count": 2},
    ]
    assert check_collective_contract(_SYNTH, contract) == []


def test_contract_violations_name_present_collectives():
    errs = check_collective_contract(_SYNTH, [
        {"op": "reduce-scatter"},                       # absent op kind
        {"op": "all-reduce", "group_size": 8},          # wrong group size
        {"op": "all-reduce", "group_size": 2, "reduce": "max"},  # add != max
    ])
    assert len(errs) == 3
    for e in errs:
        # a failed gate must name the drift, not just count it
        assert "present collectives" in e
        assert "all-gather@16[128, 32]" in e
