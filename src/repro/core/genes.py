"""Gene encoding of offload patterns (paper §3.2.1), generalized to a
multi-destination alphabet (arXiv:2011.12431 direction).

The paper's chromosome is a binary string, one gene per offloadable region:
``1`` = run the region on the accelerator, ``0`` = keep the reference path.
This module keeps that encoding as the default while letting a gene range
over a *destination alphabet* — an ordered tuple of :class:`Destination`
names such as ``("cpu", "gpu_fused", "gpu_pallas")``.  Gene value ``k``
assigns the region to alphabet entry ``k``; value 0 is always the reference
(CPU) path and value 1 the primary accelerator, so binary chromosomes keep
their exact historical meaning.

Destinations are pluggable via :func:`register_destination`.  Every
destination executes: a gene's fitness is the measured time of the program
it decodes to.  The encoding stays language/frontend-independent; frontends
only contribute the ordered site list.

The destination hierarchy is small and frozen: :class:`Device` is a single
physical device; :class:`MeshDestination` places a region on an ``n``-device
mesh along a named axis with a sharding spec (arXiv 2011.12431's mixed
offloading destinations, extended to placement × parallelism).  The wire
format IS the destination name (``mesh:data:4:batch``), so alphabets,
SeedBank records, phenotype keys, and PlanStore payloads — all of which
carry name strings — round-trip mesh specs with no schema change.  A mesh
wider than the host is never searched: frontends propose only the meshes
this host can build, and an explicit alphabet naming a wider one is refused
(:func:`repro.core.frontends.registry.resolve_alphabet`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

from repro.core.ir import RegionGraph

# ---------------------------------------------------------------------------
# destination alphabet
# ---------------------------------------------------------------------------

_PROBED_DEVICE_COUNT: Optional[int] = None


def probed_device_count() -> int:
    """How many accelerator-visible devices this process has (cached).

    Mesh destinations compare their ``n`` against this to decide whether
    the host can build them.  Falls back to 1 when jax is unavailable or
    backend init fails."""
    global _PROBED_DEVICE_COUNT
    if _PROBED_DEVICE_COUNT is None:
        try:
            import jax
            _PROBED_DEVICE_COUNT = int(jax.device_count())
        except Exception:
            _PROBED_DEVICE_COUNT = 1
    return _PROBED_DEVICE_COUNT


@dataclass(frozen=True)
class Destination:
    """One place a region can run.

    ``impl_index`` selects the site implementation the destination runs
    (0 = reference, 1 = offloaded alternative, 2+ = extra variants).

    The surface every consumer goes through:

    * ``wire()`` / ``from_wire()`` — the one serialization (the name string)
      used by gene alphabets, SeedBank cross-alphabet mapping, phenotype
      keys, and PlanStore payloads.
    * ``placement_tag`` — non-None for a mesh placement, which changes the
      phenotype beyond the decoded impl map (sharded execution of the
      reference span), so the measurement cache never conflates such
      chromosomes with the unsharded reference.
    """

    name: str
    impl_index: int = 0

    @property
    def device_count(self) -> int:
        return 1

    @property
    def placement_tag(self) -> Optional[str]:
        return None

    def wire(self) -> str:
        """Stable wire string: the name is the serialization."""
        return self.name

    @classmethod
    def from_wire(cls, wire: str) -> "Destination":
        return get_destination(wire)


@dataclass(frozen=True)
class Device(Destination):
    """A single-device placement — the scalar alphabet."""


@dataclass(frozen=True)
class MeshDestination(Destination):
    """Place a region on an ``n``-device mesh along one named axis.

    ``axis`` is the mesh axis kind — ``"data"`` shards the batch (leading)
    dimension, ``"model"`` the feature (trailing) dimension.  ``spec``
    names the sharded dimension (``"batch"``, ``"feature"``, or ``"dimK"``
    for an explicit index) and defaults from the axis.  The canonical name
    doubles as the wire format: ``mesh:{axis}:{n}:{spec}``.

    Decoding keeps ``impl_index`` 0 (the reference implementation): the
    substitution engine replaces the site's span with a mesh-sharded run of
    that same span on a host with >= ``n`` devices."""

    name: str = ""
    axis: str = "data"
    n: int = 2
    spec: str = ""

    def __post_init__(self) -> None:
        if self.axis not in ("data", "model"):
            raise ValueError(f"mesh axis must be 'data' or 'model', "
                             f"got {self.axis!r}")
        if self.n < 1:
            raise ValueError(f"mesh size must be >= 1, got {self.n}")
        spec = self.spec or ("batch" if self.axis == "data" else "feature")
        if spec not in ("batch", "feature") and not (
                spec.startswith("dim") and spec[3:].isdigit()):
            raise ValueError(f"mesh spec must be 'batch', 'feature' or "
                             f"'dimN', got {spec!r}")
        object.__setattr__(self, "spec", spec)
        object.__setattr__(self, "name", f"mesh:{self.axis}:{self.n}:{spec}")

    @property
    def device_count(self) -> int:
        return self.n

    @property
    def shard_dim(self) -> int:
        """Which dimension the spec shards: 0 (batch), -1 (feature), or K."""
        if self.spec == "batch":
            return 0
        if self.spec == "feature":
            return -1
        return int(self.spec[3:])

    def available(self) -> bool:
        """Whether this host can genuinely build the mesh."""
        return probed_device_count() >= self.n

    @property
    def placement_tag(self) -> Optional[str]:
        # mesh placement changes the phenotype (sharded execution), even
        # though the decoded impl map is the reference
        return self.name

    @classmethod
    def from_wire(cls, wire: str) -> "MeshDestination":
        parts = wire.split(":")
        if len(parts) not in (3, 4) or parts[0] != "mesh":
            raise ValueError(f"not a mesh wire string: {wire!r} "
                             f"(want 'mesh:<axis>:<n>[:<spec>]')")
        try:
            n = int(parts[2])
        except ValueError:
            raise ValueError(f"mesh size not an int in {wire!r}") from None
        return cls(axis=parts[1], n=n, spec=parts[3] if len(parts) == 4 else "")


CPU = Device("cpu", impl_index=0)
GPU = Device("gpu", impl_index=1)
#: variant destinations: same accelerator, different *implementation* of the
#: site (the kernel-substitution alphabet — a gene picks which code runs).
GPU_FUSED = Device("gpu_fused", impl_index=1)
GPU_PALLAS = Device("gpu_pallas", impl_index=2)

_DESTINATIONS: dict[str, Destination] = {
    d.name: d for d in (CPU, GPU, GPU_FUSED, GPU_PALLAS)
}

#: the paper's original binary CPU/GPU alphabet — the default everywhere.
DEFAULT_ALPHABET: tuple[str, ...] = ("cpu", "gpu")
#: the implementation-variant alphabet the measured jaxpr frontend proposes:
#: gene k selects site implementation k — reference, the fused-jnp rewrite,
#: or the Pallas kernel (see repro.kernels.registry).
VARIANT_ALPHABET: tuple[str, ...] = ("cpu", "gpu_fused", "gpu_pallas")


def register_destination(dest: Destination, replace: bool = False) -> None:
    """Add a destination to the alphabet registry (pluggable devices)."""
    if dest.name in _DESTINATIONS and not replace:
        raise ValueError(f"destination {dest.name!r} already registered")
    _DESTINATIONS[dest.name] = dest


def get_destination(name: str) -> Destination:
    dest = _DESTINATIONS.get(name)
    if dest is not None:
        return dest
    if name.startswith("mesh:"):
        # mesh wire strings are an open alphabet: parse and cache on demand
        # (under the canonical name AND the alias spelled without a spec)
        try:
            dest = MeshDestination.from_wire(name)
        except ValueError as e:
            raise KeyError(f"bad mesh destination {name!r}: {e}") from None
        _DESTINATIONS.setdefault(dest.name, dest)
        _DESTINATIONS.setdefault(name, dest)
        return dest
    raise KeyError(f"unknown destination {name!r}; registered: "
                   f"{sorted(_DESTINATIONS)}")


def destination_names() -> tuple[str, ...]:
    return tuple(sorted(_DESTINATIONS))


#: mesh sizes the frontends propose when the host has the devices for them.
MESH_PROPOSAL_SIZES: tuple[int, ...] = (2, 4, 8)


def mesh_proposals(axes: Sequence[str] = ("data",),
                   sizes: Sequence[int] = MESH_PROPOSAL_SIZES,
                   device_count: Optional[int] = None) -> tuple[str, ...]:
    """Mesh destination names this host can genuinely execute (n <= devices).

    Returns () on single-device hosts, so their alphabets and fingerprints
    carry no mesh genes."""
    ndev = probed_device_count() if device_count is None else device_count
    return tuple(MeshDestination(axis=axis, n=n).name
                 for axis in axes for n in sizes if 2 <= n <= ndev)


def with_mesh_destinations(base: Sequence[str],
                           axes: Sequence[str] = ("data",),
                           sizes: Sequence[int] = MESH_PROPOSAL_SIZES,
                           device_count: Optional[int] = None
                           ) -> tuple[str, ...]:
    """``base`` alphabet extended with this host's executable mesh genes."""
    base = tuple(base)
    return base + tuple(m for m in mesh_proposals(axes, sizes, device_count)
                        if m not in base)


# ---------------------------------------------------------------------------
# gene coding
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Site:
    """One gene position: a region plus its implementation menu.

    The first two implementations keep the paper's off/on pair; regions
    with more than one accelerated alternative (kernel-substitution
    variants) extend the menu via ``extra_impls``, indexed by
    ``Destination.impl_index`` (2 = the first extra, and so on).

    ``members`` marks a *function-block* site (arXiv 2004.09883): the named
    regions are the block's constituents.  While the block gene sits on an
    accelerated implementation it **claims** them — their own genes are
    inert and they decode to their reference path (the block adapter
    computes the whole span), so the loop-level search space shrinks to the
    unclaimed remainder.
    """

    region: str
    ref_impl: Any
    offload_impl: Any
    extra_impls: tuple = ()
    members: tuple = ()

    @property
    def impls(self) -> tuple:
        """Implementation by index — what ``Destination.impl_index`` selects."""
        return (self.ref_impl, self.offload_impl) + tuple(self.extra_impls)


@dataclass(frozen=True)
class GeneCoding:
    sites: tuple[Site, ...]
    destinations: tuple[str, ...] = DEFAULT_ALPHABET

    @property
    def length(self) -> int:
        return len(self.sites)

    @property
    def arity(self) -> int:
        """Alphabet size: how many values each gene ranges over."""
        return len(self.destinations)

    def decode(self, values: Sequence[int]) -> dict[str, Any]:
        """values -> {region name: chosen implementation}.

        Each gene decodes to the site implementation its destination's
        ``impl_index`` names, clamped to the site's menu; a mesh gene decodes
        to the reference path, which the frontend runs sharded.
        """
        assert len(values) == self.length, (len(values), self.length)
        out: dict[str, Any] = {}
        for s, v in zip(self.sites, values):
            dest = get_destination(self.destinations[int(v)])
            impls = s.impls
            out[s.region] = impls[min(dest.impl_index, len(impls) - 1)]
        claimed = self.claimed_members(values)
        if claimed:
            for s in self.sites:
                if s.region in claimed:
                    out[s.region] = s.ref_impl
        return out

    def claimed_members(self, values: Sequence[int]) -> frozenset:
        """Regions claimed by active block genes: every member of a block
        site whose gene decodes to a non-reference implementation.  Claimed
        regions' own genes are inert for this chromosome."""
        claimed: set[str] = set()
        for s, v in zip(self.sites, values):
            if not s.members:
                continue
            dest = get_destination(self.destinations[int(v)])
            impls = s.impls
            if impls[min(dest.impl_index, len(impls) - 1)] != s.ref_impl:
                claimed.update(s.members)
        return frozenset(claimed)

    def destinations_of(self, values: Sequence[int]) -> dict[str, str]:
        """values -> {region name: destination name}."""
        assert len(values) == self.length, (len(values), self.length)
        return {s.region: self.destinations[int(v)]
                for s, v in zip(self.sites, values)}

    def all_off(self) -> tuple[int, ...]:
        return (0,) * self.length

    def all_on(self) -> tuple[int, ...]:
        return (1,) * self.length


def coding_from_graph(graph: RegionGraph,
                      exclude: Sequence[str] = (),
                      destinations: Sequence[str] = DEFAULT_ALPHABET
                      ) -> GeneCoding:
    """Build the gene coding from a region graph's offloadable regions,
    excluding regions already claimed by the function-block pass (paper
    §4.2: ループ文オフロードはオフロード可能だった機能ブロック部分を抜いた
    コードに対して試行)."""
    for d in destinations:
        get_destination(d)           # fail fast on unknown alphabet entries
    sites = []
    for r in graph.offloadable():
        if r.name in exclude:
            continue
        ref = r.alternatives[0] if r.alternatives else "ref"
        off = r.alternatives[1] if len(r.alternatives) > 1 else "offload"
        sites.append(Site(r.name, ref, off, tuple(r.alternatives[2:]),
                          members=tuple(r.meta.get("block_members", ()))))
    return GeneCoding(tuple(sites), tuple(destinations))
