"""Execution placement for the ``repro.dpp`` facade: where DPP work runs.

One ``Runtime`` object owns every placement decision the repo used to
scatter across ``backend="device"|"host"`` strings, a ``--distributed``
CLI flag, and ad-hoc ``mesh=`` keyword plumbing:

``Local()``
    single-device execution (the default) — every array lives on the
    process' default device and batched work is one jit+vmap call.
``Mesh(axes={"data": n})``
    SPMD execution over a jax device mesh: PRNG-key batches are sharded
    over the data axes (``shard_map``), subset batches are placed sharded,
    and learning-side reductions (Θ-statistics, acceptance
    log-likelihoods) are ``psum``'d over the data axes. The per-sample /
    per-subset arithmetic is IDENTICAL to ``Local`` — a mesh partitions
    work, it never changes the math — so sampling draws reproduce the
    local ones bit-for-bit on shared keys.
``Host()``
    the numpy reference oracle (``core.sampling``) — one eigh + one
    host-loop subset per draw. Kept as the ground-truth slow path.

Consumers never import jax sharding machinery: they take ``runtime=`` and
call the methods here. Anything placement-shaped that future scaling items
need (sharded phase-1 spectra, cross-host collectives) lands on this seam.

This module deliberately imports nothing from the rest of ``repro.dpp``
(models import it, not vice versa), so subsystem code
(``repro.sampling``, ``repro.learning``) can depend on it cycle-free.
"""

from __future__ import annotations

import collections
import dataclasses
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..core.distributed import shard_map_unchecked


class Runtime:
    """Shared protocol for execution placements. ``kind`` is the stable
    discriminator subsystem code dispatches on (no isinstance chains, so
    duck-typed runtimes keep working across module reloads)."""

    kind: str = "local"

    #: True when batched device work should go through ``map_keys``/
    #: ``shard_batch`` instead of one flat call.
    @property
    def is_mesh(self) -> bool:
        return self.kind == "mesh"

    def map_keys(self, fn, keys: jax.Array, operands=(), static_key=None):
        """Run ``fn(keys, operands)`` (pure; returns arrays whose leading
        dim matches ``keys``) under this placement. ``operands`` carries
        every array input (replicated under a mesh); ``static_key`` names
        ``fn``'s static config for executable caching (see ``Mesh``)."""
        return fn(keys, operands)

    def __repr__(self) -> str:
        return f"{type(self).__name__}()"


@dataclasses.dataclass(frozen=True)
class Local(Runtime):
    """Single-device execution — the default placement everywhere."""
    kind = "local"


@dataclasses.dataclass(frozen=True)
class Host(Runtime):
    """The numpy reference oracle (plain-DPP sampling only)."""
    kind = "host"


class Mesh(Runtime):
    """SPMD placement over a jax device mesh.

    axes: ordered ``{axis_name: size}`` — e.g. ``{"data": 8}`` or
        ``{"data": 4, "model": 2}``. Every axis except ``"model"`` shards
        data (batches of PRNG keys / training subsets); ``"model"`` is
        reserved for tensor-parallel factor updates
        (``core.distributed.make_distributed_krk_step(shard_updates=)``).
    devices: optional explicit device list (defaults to ``jax.devices()``,
        taking the first prod(axes) of them).
    jax_mesh: adopt an existing ``jax.sharding.Mesh`` instead (axes/devices
        are then ignored).

    The underlying ``jax.sharding.Mesh`` is built lazily on first use so
    constructing a ``Mesh`` spec never touches jax device state at import
    time (required by the smoke tests that must see exactly one device
    until they fork).
    """

    kind = "mesh"

    def __init__(self, axes: Optional[Dict[str, int]] = None, *,
                 devices=None, jax_mesh=None):
        if axes is None and jax_mesh is None:
            axes = {"data": -1}          # -1: all available devices
        self._axes = dict(axes) if axes is not None else None
        self._devices = devices
        self._mesh = jax_mesh
        #: static_key -> jitted shard_map'd sampler (see ``map_keys``)
        self._mapped_cache: Dict = {}
        #: id(array) -> (source ref, replicated copy) for long-lived
        #: arrays (cached spectra); see ``replicate_pinned``
        self._pinned = collections.OrderedDict()

    @classmethod
    def from_jax_mesh(cls, mesh) -> "Mesh":
        """Adopt an already-built ``jax.sharding.Mesh`` (the legacy
        ``fit(mesh=...)`` plumbing lands here)."""
        return cls(jax_mesh=mesh)

    # -- mesh construction --------------------------------------------------
    @property
    def mesh(self):
        if self._mesh is None:
            devs = list(self._devices if self._devices is not None
                        else jax.devices())
            axes = dict(self._axes)
            for name, size in axes.items():
                if size == -1:
                    fixed = int(np.prod([s for s in axes.values() if s != -1]))
                    axes[name] = max(1, len(devs) // max(1, fixed))
            shape = tuple(axes.values())
            n = int(np.prod(shape))
            if len(devs) < n:
                raise ValueError(
                    f"Mesh(axes={axes}) needs {n} devices, "
                    f"have {len(devs)} — under CPU set "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
                    f"before importing jax")
            self._mesh = jax.sharding.Mesh(
                np.asarray(devs[:n]).reshape(shape), tuple(axes.keys()))
        return self._mesh

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Axes that shard data batches — everything but ``model``."""
        return tuple(a for a in self.mesh.axis_names if a != "model")

    @property
    def num_data_shards(self) -> int:
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return int(np.prod([shape[a] for a in self.data_axes]))

    def __repr__(self) -> str:
        if self._mesh is not None:
            shape = dict(zip(self._mesh.axis_names, self._mesh.devices.shape))
            return f"Mesh(axes={shape})"
        return f"Mesh(axes={self._axes})"

    # -- placement primitives ------------------------------------------------
    def shard_map(self, f, in_specs, out_specs):
        """``shard_map`` over this mesh (replication checks off — outputs
        declared replicated are replicated by construction)."""
        return shard_map_unchecked(f, self.mesh, in_specs, out_specs)

    def data_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.data_axes))

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def replicate(self, tree):
        """Place every array leaf replicated over the mesh."""
        sh = self.replicated_sharding()
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)

    _PINNED_MAX = 64

    def replicate_pinned(self, arrays: Tuple[jax.Array, ...]
                         ) -> Tuple[jax.Array, ...]:
        """``replicate`` with an identity-keyed LRU cache (strong refs pin
        the ids, mirroring ``SpectralCache``) — for long-lived arrays like
        cached spectra, so repeated placement is a dict hit instead of a
        fresh host -> all-devices broadcast on every sampling call. Do NOT
        use for per-step arrays (learner params): every new array would
        make a new entry."""
        out = []
        for x in arrays:
            key = id(x)
            hit = self._pinned.get(key)
            if hit is None or hit[0] is not x:
                hit = (x, jax.device_put(x, self.replicated_sharding()))
                self._pinned[key] = hit
                while len(self._pinned) > self._PINNED_MAX:
                    self._pinned.popitem(last=False)
            else:
                self._pinned.move_to_end(key)
            out.append(hit[1])
        return tuple(out)

    def shard_batch(self, batch):
        """Place a ``SubsetBatch`` sharded over the data axes on dim 0
        (``even_batch`` first when n does not divide the shard count)."""
        from ..core.distributed import shard_subsets
        return shard_subsets(self.mesh, batch, self.data_axes)

    def even_batch(self, batch):
        """Trim a ``SubsetBatch`` to the largest length divisible by the
        data-shard count (``shard_map`` needs even shards)."""
        from ..core.dpp import SubsetBatch
        n = batch.indices.shape[0]
        keep = n - n % self.num_data_shards
        if keep == n:
            return batch
        if keep == 0:
            raise ValueError(
                f"batch of {n} subsets cannot be sharded over "
                f"{self.num_data_shards} data shards")
        trunc = getattr(batch, "truncated", None)
        return SubsetBatch(batch.indices[:keep], batch.mask[:keep],
                           None if trunc is None else trunc[:keep])

    # -- the sampling seam ---------------------------------------------------
    def map_keys(self, fn, keys: jax.Array, operands=(), static_key=None):
        """Shard a batch of PRNG keys over the data axes and run
        ``fn(keys_shard, operands)`` on each shard (one launch for the
        whole batch; ``operands`` — e.g. spectrum arrays — replicated).

        ``fn`` must be pure and per-key independent (every sampler in
        ``repro.sampling`` is), so the result equals the unsharded
        ``fn(keys, operands)`` draw-for-draw. Key counts that do not
        divide the shard count are padded with repeated keys and the
        padded rows are sliced off — so shard-count changes never alter
        what callers see, and per-row statistics (truncation flags) are
        never double-counted from padding.

        ``static_key`` (a hashable tag of ``fn``'s static config — its
        name plus every baked-in static) enables executable caching:
        the jitted shard_map program is cached on this Mesh per
        ``static_key`` and per argument shape, so repeated calls at one
        shape reuse the compiled executable instead of retracing — the
        same one-compile-per-shape contract the Local samplers keep. A
        cached ``fn`` must close over NOTHING but static config; every
        array input has to flow through ``operands``.
        """
        n = int(keys.shape[0])
        shards = self.num_data_shards
        pad = (-n) % shards
        if pad:
            keys = keys[jnp.arange(n + pad) % n]
        spec = P(self.data_axes)
        tracker = obs.current_tracker()
        if static_key is not None:
            mapped = self._mapped_cache.get(static_key)
            if mapped is None:
                tracker.counter("runtime.mesh.exec_cache_misses")
                mapped = jax.jit(self.shard_map(
                    fn, in_specs=(spec, P()), out_specs=spec))
                self._mapped_cache[static_key] = mapped
            else:
                tracker.counter("runtime.mesh.exec_cache_hits")
        else:
            mapped = self.shard_map(fn, in_specs=(spec, P()),
                                    out_specs=spec)
        if obs.enabled(tracker):
            # span + block_until_ready mirror the SpectralCache eigh
            # pattern: the sync exists only to make the span an honest
            # wall-clock sample, and only when someone is listening
            with obs.spans.start_span("runtime.mesh.map_keys",
                                      tracker=tracker, keys=n,
                                      shards=shards):
                out = jax.block_until_ready(mapped(keys, operands))
        else:
            out = mapped(keys, operands)
        if pad:
            out = jax.tree_util.tree_map(lambda x: x[:n], out)
        # emitted AFTER the pad slice, so per-shard row stats downstream
        # consumers derive (e.g. ServiceStats.truncations) and the counts
        # here agree on what a "row" is: real keys only, all shards
        if obs.enabled(tracker):
            tracker.counter("runtime.mesh.map_keys_calls")
            tracker.counter("runtime.mesh.keys", n)
            tracker.counter("runtime.mesh.pad_rows", pad)
            tracker.gauge("runtime.mesh.data_shards", shards)
        return out


# ---------------------------------------------------------------------------
# Resolution / CLI helpers
# ---------------------------------------------------------------------------

def default_runtime() -> Runtime:
    return Local()


def from_spec(spec: "str | Runtime | None") -> Runtime:
    """CLI-friendly constructor: ``"local"`` / ``"host"`` / ``"mesh"``
    (all devices on one ``data`` axis) or an existing ``Runtime``."""
    if spec is None:
        return Local()
    if isinstance(spec, Runtime):
        return spec
    name = str(spec).lower()
    if name == "local":
        return Local()
    if name == "host":
        return Host()
    if name == "mesh":
        return Mesh()
    raise ValueError(f"unknown runtime spec {spec!r}; "
                     f"expected 'local', 'host' or 'mesh'")


def resolve(runtime: Optional[Runtime] = None, *,
            backend: Optional[str] = None,
            mesh=None, stacklevel: int = 3) -> Runtime:
    """One resolution point for the deprecated placement spellings.

    ``backend="device"|"host"`` (pre-runtime sampler strings) and
    ``mesh=<jax Mesh>`` (pre-runtime fit plumbing) warn and map onto
    runtimes; passing either together with ``runtime=`` is an error —
    there must be exactly one source of placement truth.
    """
    legacy = []
    if backend is not None:
        if backend not in ("device", "host"):
            raise ValueError(f"backend must be 'device' or 'host', "
                             f"got {backend!r}")
        warnings.warn(
            "backend= placement strings are deprecated; pass "
            "runtime=repro.dpp.runtime.Local() (was backend='device') or "
            "runtime=repro.dpp.runtime.Host() (was backend='host')",
            DeprecationWarning, stacklevel=stacklevel)
        legacy.append(Host() if backend == "host" else Local())
    if mesh is not None:
        warnings.warn(
            "mesh= is deprecated; pass "
            "runtime=repro.dpp.runtime.Mesh.from_jax_mesh(mesh) (or "
            "runtime=Mesh(axes={'data': n}))",
            DeprecationWarning, stacklevel=stacklevel)
        legacy.append(Mesh.from_jax_mesh(mesh))
    if legacy:
        if runtime is not None or len(legacy) > 1:
            raise ValueError(
                "conflicting placements: pass exactly one of runtime=, "
                "backend= (deprecated) or mesh= (deprecated)")
        return legacy[0]
    if isinstance(runtime, str):
        if runtime in ("device", "host"):
            # a pre-runtime backend string in the runtime slot — the shape
            # legacy POSITIONAL callers of the old backend= parameters
            # produce; honor the shim contract rather than TypeError-ing
            return resolve(backend=runtime, stacklevel=stacklevel + 1)
        raise TypeError(
            f"runtime= wants a Runtime object, got the string {runtime!r} "
            f"— use repro.dpp.runtime.from_spec({runtime!r}) for CLI-style "
            f"specs")
    if runtime is None:
        return Local()
    if not isinstance(runtime, Runtime) and not hasattr(runtime, "kind"):
        hint = ""
        if isinstance(runtime, jax.sharding.Mesh):
            hint = (" — wrap a raw jax Mesh with "
                    "repro.dpp.runtime.Mesh.from_jax_mesh(mesh)")
        raise TypeError(
            f"runtime= wants a repro.dpp.runtime Runtime, got "
            f"{type(runtime).__name__}{hint}")
    return runtime
