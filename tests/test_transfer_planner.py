"""Transfer planner: the paper's def/use transfer rule + hoisting, checked on
hand-built IR and property-tested for safety invariants."""
from hypothesis import given, settings, strategies as st

from repro.core.ir import Region, RegionGraph
from repro.core.transfer_planner import plan_transfers


def _loop(name, defs=(), uses=(), parent=None, kind="loop", trip=None):
    return Region(name=name, kind=kind, parent=parent,
                  defs=frozenset(defs), uses=frozenset(uses),
                  offloadable=(kind == "loop"), trip_count=trip,
                  alternatives=("interp", "jit"))


def test_h2d_for_device_consumed_var():
    g = RegionGraph([
        _loop("s0", defs={"x"}, kind="stmt"),
        _loop("l1", uses={"x"}, defs={"y"}),
    ], "python_ast")
    plan = plan_transfers(g, {"l1": "jit"})
    assert any(t.var == "x" and t.direction == "h2d" for t in plan.transfers)


def test_d2h_when_host_reads_device_result():
    g = RegionGraph([
        _loop("l1", uses={"x"}, defs={"y"}),
        _loop("s2", uses={"y"}, defs={"z"}, kind="stmt"),
    ], "python_ast")
    plan = plan_transfers(g, {"l1": "jit"})
    assert any(t.var == "y" and t.direction == "d2h" for t in plan.transfers)


def test_no_transfer_between_consecutive_device_regions():
    g = RegionGraph([
        _loop("l1", uses={"x"}, defs={"y"}),
        _loop("l2", uses={"y"}, defs={"z"}),
    ], "python_ast")
    plan = plan_transfers(g, {"l1": "jit", "l2": "jit"})
    assert not any(t.var == "y" and t.direction == "d2h" for t in plan.transfers)


def test_hoist_invariant_transfer_out_of_loop():
    # outer interpreted loop; inner offloaded uses loop-invariant `w`
    g = RegionGraph([
        _loop("outer", uses={"w"}, defs={"i"}, trip=10),
        _loop("inner", uses={"w", "i"}, defs={"acc"}, parent="outer"),
    ], "python_ast")
    plan = plan_transfers(g, {"inner": "jit"}, hoist=True)
    t = next(t for t in plan.transfers if t.var == "w" and t.direction == "h2d")
    assert t.at_region == "outer" and t.hoisted_from is not None
    plan2 = plan_transfers(g, {"inner": "jit"}, hoist=False)
    t2 = next(t for t in plan2.transfers if t.var == "w")
    assert t2.per_iteration


def test_host_mutated_var_not_hoisted():
    # sibling host stmt writes `w` every iteration -> must transfer per iter
    g = RegionGraph([
        _loop("outer", uses={"w"}, defs={"i"}, trip=5),
        _loop("mut", defs={"w"}, parent="outer", kind="stmt"),
        _loop("inner", uses={"w"}, defs={"acc"}, parent="outer"),
    ], "python_ast")
    plan = plan_transfers(g, {"inner": "jit"}, hoist=True)
    t = next(t for t in plan.transfers if t.var == "w" and t.direction == "h2d")
    assert t.at_region == "inner"  # could not hoist past the mutation


@given(st.lists(st.sampled_from(["jit", "interp"]), min_size=1, max_size=6),
       st.integers(0, 3))
@settings(max_examples=30, deadline=None)
def test_property_chain_safety(impls, n_vars):
    """For a linear chain r0->r1->... where r_{i} defines v_i and uses
    v_{i-1}: every device-consumed var has an h2d upstream or a device def,
    and every host-consumed device-def has a d2h."""
    regions = []
    for i, im in enumerate(impls):
        regions.append(_loop(f"r{i}", defs={f"v{i}"},
                             uses={f"v{i-1}"} if i else {"inp"}))
    g = RegionGraph(regions, "python_ast")
    impl = {f"r{i}": im for i, im in enumerate(impls)}
    plan = plan_transfers(g, impl)
    on_dev = set()
    transfers = list(plan.transfers)
    for i, im in enumerate(impls):
        r = g.by_name(f"r{i}")
        if im == "jit":
            for u in r.uses:
                assert u in on_dev or any(
                    t.var == u and t.direction == "h2d" for t in transfers)
            on_dev |= r.defs
        else:
            for u in r.uses & on_dev:
                assert any(t.var == u and t.direction == "d2h" for t in transfers)
            on_dev -= r.defs
