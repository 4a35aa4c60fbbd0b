"""Property-based gene-coding tests over *arbitrary* destination alphabets.

The shipped alphabets (binary, variant, with or without meshes) are points
in the space the encoding must cover; these properties hold for any alphabet built
from registered destinations: decode totality + ``impl_index`` clamping on
sites with short implementation menus, decode/encode (``destinations_of``)
round-trip, cross-alphabet seed-value mapping, and phenotype-key
consistency (decode-equivalent chromosomes share a key).

Property tests run under hypothesis; the example-based sections at the
bottom pin the same rules on fixed cases.
"""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.genes import (VARIANT_ALPHABET, Destination, GeneCoding,
                              Site, coding_from_graph, get_destination,
                              register_destination)
from repro.core.ir import Region, RegionGraph
from repro.core.offload import _map_destination_value, phenotype_key

# a pool of synthetic destinations covering impl_index 0..4, so alphabets
# are *arbitrary*, not just the shipped ones
for _i in range(5):
    try:
        register_destination(Destination(f"xdev{_i}", impl_index=_i))
    except ValueError:
        pass                       # already registered by a previous import

# mesh wire names are first-class alphabet entries: the properties must
# hold with them mixed in
ALPHA_POOL = ("cpu", "gpu", "gpu_fused", "gpu_pallas",
              "xdev0", "xdev1", "xdev2", "xdev3", "xdev4",
              "mesh:data:4:batch", "mesh:model:2:feature")


def _sites(extra_counts):
    return tuple(
        Site(f"r{i}", "ref", "off",
             tuple(f"e{i}_{j}" for j in range(k)))
        for i, k in enumerate(extra_counts))


alphabets = st.lists(st.sampled_from(ALPHA_POOL), min_size=2, max_size=6,
                     unique=True).map(tuple)
site_menus = st.lists(st.integers(0, 3), min_size=1, max_size=5)


@given(alphabet=alphabets, extras=site_menus, data=st.data())
@settings(max_examples=60, deadline=None)
def test_decode_is_total_and_clamps(alphabet, extras, data):
    coding = GeneCoding(_sites(extras), alphabet)
    values = data.draw(st.lists(st.integers(0, coding.arity - 1),
                                min_size=coding.length,
                                max_size=coding.length))
    decoded = coding.decode(values)
    for s, v in zip(coding.sites, values):
        dest = get_destination(alphabet[v])
        impls = s.impls
        # clamping: an impl_index beyond the menu selects the last impl,
        # and decode never raises or invents an implementation
        assert decoded[s.region] == impls[min(dest.impl_index,
                                              len(impls) - 1)]
        assert decoded[s.region] in impls


@given(alphabet=alphabets, extras=site_menus, data=st.data())
@settings(max_examples=60, deadline=None)
def test_destinations_of_roundtrips_values(alphabet, extras, data):
    coding = GeneCoding(_sites(extras), alphabet)
    values = data.draw(st.lists(st.integers(0, coding.arity - 1),
                                min_size=coding.length,
                                max_size=coding.length))
    names = coding.destinations_of(values)
    # encode(decode) round-trip: unique alphabets map names back to values
    assert [alphabet.index(names[s.region]) for s in coding.sites] == values


@given(alphabet=alphabets, rec=st.lists(st.sampled_from(ALPHA_POOL),
                                        min_size=0, max_size=6).map(tuple),
       value=st.integers(-3, 9))
@settings(max_examples=80, deadline=None)
def test_cross_alphabet_seed_mapping_is_total(alphabet, rec, value):
    coding = GeneCoding(_sites([1]), alphabet)
    mapped = _map_destination_value(value, rec, coding)
    assert 0 <= mapped < coding.arity, "mapped seed must be a legal gene"
    if not rec:
        assert mapped == min(max(value, 0), coding.arity - 1)
    elif 0 <= value < len(rec):
        name = rec[value]
        if name in alphabet:
            assert mapped == alphabet.index(name)       # name-faithful
        elif value == 0:
            assert mapped == 0                          # ref stays ref
        else:
            assert mapped == (1 if coding.arity > 1 else 0)
    else:
        assert mapped == 0                              # corrupt record


@given(alphabet=alphabets, extras=site_menus, data=st.data())
@settings(max_examples=60, deadline=None)
def test_phenotype_key_matches_decode_equivalence(alphabet, extras, data):
    coding = GeneCoding(_sites(extras), alphabet)
    key = phenotype_key(coding)
    draw = lambda: tuple(data.draw(st.lists(  # noqa: E731
        st.integers(0, coding.arity - 1), min_size=coding.length,
        max_size=coding.length)))
    v1, v2 = draw(), draw()

    def pheno(values):
        return (tuple(sorted(coding.decode(values).items())),
                tuple((s.region, alphabet[v])
                      for s, v in zip(coding.sites, values)
                      if get_destination(alphabet[v]).placement_tag
                      is not None))

    assert (key(v1) == key(v2)) == (pheno(v1) == pheno(v2))


# ---------------------------------------------------------------------------
# example-based anchors
# ---------------------------------------------------------------------------


def _graph():
    return RegionGraph([
        Region("two", "loop", offloadable=True,
               alternatives=("ref", "kernel")),
        Region("three", "loop", offloadable=True,
               alternatives=("ref", "fused_jnp", "pallas")),
    ], "ir", "props")


def test_clamped_impl_index_aliases_to_last_impl():
    coding = coding_from_graph(_graph(),
                               destinations=("cpu", "gpu_fused",
                                             "gpu_pallas"))
    d1, d2 = coding.decode((1, 1)), coding.decode((2, 2))
    assert d1["two"] == d2["two"] == "kernel"       # clamped on the 2-menu
    assert d1["three"] == "fused_jnp" and d2["three"] == "pallas"


def test_phenotype_key_equates_clamped_chromosomes_only():
    coding = coding_from_graph(_graph(),
                               destinations=("cpu", "gpu_fused",
                                             "gpu_pallas"))
    key = phenotype_key(coding)
    assert key((1, 0)) == key((2, 0)), "clamped genes decode identically"
    assert key((0, 1)) != key((0, 2)), "real variants stay distinct"
    assert key((0, 0)) != key((1, 0))


def test_phenotype_key_separates_cost_only_parking():
    # parking a gene on a destination that decodes to the reference impl
    # (a mesh placement) is still a different program from leaving it on
    # the reference path: different phenotype, different measurement
    coding = coding_from_graph(_graph(),
                               destinations=VARIANT_ALPHABET
                               + ("mesh:data:2:batch",))
    key = phenotype_key(coding)
    assert coding.decode((3, 0)) == coding.decode((0, 0))
    assert key((0, 0)) != key((3, 0))
    assert key((0, 0)) != key((2, 0))


def test_foreign_bits_never_crash_phenotype_key():
    coding = coding_from_graph(_graph())
    key = phenotype_key(coding)
    assert key((1,)) == ("raw", (1,))        # stale persisted line


# ---------------------------------------------------------------------------
# function-block genes: claiming semantics
# ---------------------------------------------------------------------------


def _block_graph():
    return RegionGraph([
        Region("a", "loop", offloadable=True,
               alternatives=("ref", "kernel"), trip_count=8),
        Region("b", "loop", offloadable=True,
               alternatives=("ref", "fused_jnp", "pallas"), trip_count=8),
        Region("c", "loop", offloadable=True,
               alternatives=("ref", "kernel"), trip_count=8),
        Region("blk", "block", offloadable=True,
               alternatives=("ref", "block_chunked", "block_fused"),
               meta={"block_members": ("a", "b")}),
    ], "ir", "block-props")


def test_coding_from_graph_carries_block_members():
    coding = coding_from_graph(_block_graph())
    by_region = {s.region: s for s in coding.sites}
    assert by_region["blk"].members == ("a", "b")
    assert all(not s.members for r, s in by_region.items() if r != "blk")


def test_active_block_gene_claims_members_to_ref():
    coding = coding_from_graph(_block_graph(),
                               destinations=("cpu", "gpu_fused",
                                             "gpu_pallas"))
    order = [s.region for s in coding.sites]
    values = tuple(1 for _ in order)            # everything on, block too
    assert coding.claimed_members(values) == frozenset({"a", "b"})
    decoded = coding.decode(values)
    # claimed members are inert — forced to their reference path even
    # though their own genes are on
    assert decoded["a"] == "ref" and decoded["b"] == "ref"
    assert decoded["c"] == "kernel"             # unclaimed keeps its gene
    assert decoded["blk"] == "block_chunked"


def test_inactive_block_gene_claims_nothing():
    coding = coding_from_graph(_block_graph(),
                               destinations=("cpu", "gpu_fused",
                                             "gpu_pallas"))
    order = [s.region for s in coding.sites]
    values = tuple(0 if r == "blk" else 1 for r in order)
    assert coding.claimed_members(values) == frozenset()
    decoded = coding.decode(values)
    assert decoded == {"a": "kernel", "b": "fused_jnp", "c": "kernel",
                       "blk": "ref"}


def test_phenotype_key_ignores_claimed_member_genes():
    coding = coding_from_graph(_block_graph(),
                               destinations=("cpu", "gpu_fused",
                                             "gpu_pallas"))
    key = phenotype_key(coding)
    order = [s.region for s in coding.sites]

    def chrom(**genes):
        return tuple(genes.get(r, 0) for r in order)

    # with the block gene active, the members' own genes cannot change the
    # program — one phenotype, one measurement
    assert key(chrom(blk=1)) == key(chrom(blk=1, a=1, b=2))
    # with the block gene off they are live again
    assert key(chrom()) != key(chrom(a=1))
    # and block on vs off is of course a different program
    assert key(chrom(blk=1)) != key(chrom())


@given(data=st.data())
@settings(max_examples=40, deadline=None)
def test_block_claiming_property_members_always_ref(data):
    coding = coding_from_graph(_block_graph(),
                               destinations=("cpu", "gpu_fused",
                                             "gpu_pallas"))
    values = tuple(data.draw(st.lists(st.integers(0, coding.arity - 1),
                                      min_size=coding.length,
                                      max_size=coding.length)))
    decoded = coding.decode(values)
    claimed = coding.claimed_members(values)
    for region in claimed:
        site = next(s for s in coding.sites if s.region == region)
        assert decoded[region] == site.ref_impl
    blk = next(s for s in coding.sites if s.region == "blk")
    if decoded["blk"] != blk.ref_impl:
        assert claimed == frozenset(blk.members)
    else:
        assert claimed == frozenset()


@pytest.mark.parametrize("value,rec,expect", [
    (1, ("cpu", "gpu"), 1),                  # same alphabet
    (1, ("cpu", "gpu_pallas"), 1),           # offloaded name missing -> slot 1
    (0, ("cpu", "gpu"), 0),                  # ref stays ref
    (5, ("cpu", "gpu"), 0),                  # corrupt record
    (3, (), 1),                              # legacy clamp
    (-2, (), 0),                             # legacy clamp, lower bound
])
def test_map_destination_value_examples(value, rec, expect):
    coding = coding_from_graph(_graph())     # binary cpu/gpu
    assert _map_destination_value(value, rec, coding) == expect


# ---------------------------------------------------------------------------
# mesh destinations (Destination v2)
# ---------------------------------------------------------------------------


def test_mesh_wire_roundtrip():
    from repro.core.genes import Destination, MeshDestination

    d = MeshDestination(axis="data", n=4)
    assert d.name == d.wire() == "mesh:data:4:batch"
    assert d.device_count == 4 and d.shard_dim == 0
    assert MeshDestination.from_wire(d.wire()) == d
    assert Destination.from_wire(d.wire()) == d       # base-class entry too
    # model axis defaults to a feature-dim spec
    m = MeshDestination(axis="model", n=2)
    assert m.name == "mesh:model:2:feature" and m.shard_dim == -1
    assert get_destination("mesh:model:2:feature") == m
    # explicit dim specs parse
    k = MeshDestination.from_wire("mesh:data:2:dim1")
    assert k.shard_dim == 1


@pytest.mark.parametrize("wire", [
    "mesh:diag:2:batch",        # unknown axis
    "mesh:data:0:batch",        # no devices
    "mesh:data:two:batch",      # non-integer n
    "mesh:data:2:cols",         # unknown spec
    "mesh:data",                # too few fields
])
def test_mesh_bad_wire_raises(wire):
    from repro.core.genes import MeshDestination

    with pytest.raises(ValueError):
        MeshDestination.from_wire(wire)
    with pytest.raises(KeyError):
        get_destination(wire)


def test_mesh_gene_decodes_to_ref_and_tags_phenotype():
    coding = coding_from_graph(_graph(),
                               destinations=("cpu", "gpu",
                                             "mesh:data:4:batch"))
    # a mesh gene never invents an implementation: the decoded impl map is
    # the reference path (the frontend realizes sharding)
    decoded = coding.decode((2, 2))
    assert decoded == {"two": "ref", "three": "ref"}
    key = phenotype_key(coding)
    # ...but placement changes the phenotype: all-ref, one gene and two
    # genes on the mesh are three different programs
    assert key((0, 0)) != key((2, 0))
    assert key((2, 0)) != key((2, 2))


def test_mesh_proposals_respect_device_count():
    from repro.core.genes import mesh_proposals, with_mesh_destinations

    assert mesh_proposals(device_count=1) == ()
    assert mesh_proposals(device_count=4) == ("mesh:data:2:batch",
                                              "mesh:data:4:batch")
    assert with_mesh_destinations(("cpu", "gpu"), device_count=8) == (
        "cpu", "gpu", "mesh:data:2:batch", "mesh:data:4:batch",
        "mesh:data:8:batch")
    assert mesh_proposals(axes=("data", "model"), device_count=2) == \
        ("mesh:data:2:batch", "mesh:model:2:feature")
    # with_mesh_destinations extends an alphabet without duplicates
    ext = with_mesh_destinations(VARIANT_ALPHABET, device_count=4)
    assert ext[:len(VARIANT_ALPHABET)] == VARIANT_ALPHABET
    assert ext == with_mesh_destinations(ext, device_count=4)[:len(ext)]
    # single-device host: alphabet unchanged (CI fingerprints stay stable)
    assert with_mesh_destinations(VARIANT_ALPHABET, device_count=1) == \
        VARIANT_ALPHABET
