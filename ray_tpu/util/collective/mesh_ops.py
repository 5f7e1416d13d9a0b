"""ICI-native collective engine: the group IS a named Mesh, and every group
op is one compiled shard_map program over it.

SURVEY.md §7: on TPU, ICI collectives only exist *inside compiled programs* —
a host-mediated rendezvous actor can be correct but never fast. This module
lowers each collective to the corresponding `jax.lax` primitive under
shard_map:

    allreduce      -> lax.psum / lax.pmax / lax.pmin
                      (PRODUCT: all_gather + prod — jax has no pprod)
    allgather      -> lax.all_gather          (retires the one-hot world×
                                               host buffer the old path built)
    reducescatter  -> lax.psum_scatter        (SUM; other ops reduce+slice)
    broadcast      -> log2(world) ppermute tree (jax.lax.ppermute requires
                      unique sources, so one-to-many is a doubling tree)
    send/recv      -> lax.ppermute [(src, dst)]
    barrier        -> tiny psum

Compiled programs are cached per `(op, shape, dtype, extras)` on the engine,
and device staging is cached by input-buffer identity so repeated collectives
on the same host buffer skip the per-call np.asarray + device_put round trip
entirely (`stage_local` / `stage_parts`).

Single-controller (tests, benchmarks): build the engine over a 1-D mesh of
all local devices and stage every rank's contribution with `stage_parts`.
Multi-controller (TPU pods): each jax.distributed process owns one device of
the group's ici mesh and stages only its own shard with `stage_local`.

NOTE on the staging cache: a hit requires the SAME array object (identity,
held by weakref) — mutating a cached buffer in place and re-issuing the
collective is safe because numpy arrays passed to jax are copied at
device_put time, but the cache would then serve the OLD bytes. Call
`invalidate(arr)` (or pass a fresh array) after in-place mutation.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

SUM, PRODUCT, MIN, MAX = "sum", "product", "min", "max"

_STAGE_CACHE_CAP = 32

# -- telemetry (docs/observability.md) ----------------------------------------
_LAT = None
_BYTES = None


def _observe(op: str, group: str, nbytes: int, dt: float) -> None:
    global _LAT, _BYTES
    if _LAT is None:
        from ray_tpu._private import telemetry

        _LAT = telemetry.histogram(
            "collective",
            "op_latency_s",
            "wall time of one compiled group op (stage + dispatch + sync)",
            buckets=telemetry.LATENCY_BUCKETS_S,
        )
        _BYTES = telemetry.counter(
            "collective",
            "bytes",
            "payload bytes contributed per rank through group ops",
        )
    _LAT.cell(op=op, group=group).observe(dt)
    _BYTES.cell(op=op, group=group).inc(nbytes)
    # Every collective funnels through here (store + xla backends), so this
    # is the one place a group op becomes a trace span when the caller is
    # inside a traced task.
    from ray_tpu._private import rpc

    if rpc._trace_ctx.get() is not None:
        import time as _time

        from ray_tpu.util import tracing

        tracing.record_span(
            f"collective.{op}",
            "collective",
            _time.time() - dt,
            dt,
            group=group,
            nbytes=nbytes,
        )


class MeshCollectives:
    """Compiled group ops over one mesh axis.

    The mesh's `axis` dimension is the rank dimension: device i along it is
    rank i. All op inputs are "staged" global arrays of shape
    ``(world,) + local_shape`` sharded ``P(axis)`` — one row per rank.
    """

    def __init__(self, mesh, axis: str = "world", group_name: str = "default"):
        self.mesh = mesh
        self.axis = axis
        self.group_name = group_name
        self.world = int(mesh.shape[axis])
        self._programs: Dict[tuple, Any] = {}
        self._shardings: Dict[tuple, Any] = {}
        # identity-keyed device staging cache: (id, rank) -> (wref, staged)
        self._staged: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._barrier_input = None
        # host-staging accounting, asserted by the allgather regression test:
        # staged_bytes counts host->device bytes actually copied (cache
        # misses only), so an allgather of a 1 MiB shard adds 1 MiB — not
        # world x 1 MiB like the retired one-hot expansion did.
        self.stats = {"staged_bytes": 0, "stage_hits": 0, "stage_misses": 0}

    # -- sharding / program caches -------------------------------------------

    def _sharding(self, *parts):
        key = parts
        s = self._shardings.get(key)
        if s is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            s = NamedSharding(self.mesh, P(*parts))
            self._shardings[key] = s
        return s

    def _program(self, key: tuple, build):
        fn = self._programs.get(key)
        if fn is None:
            fn = build()
            self._programs[key] = fn
        return fn

    def _smap(self, body, out_parts):
        import jax
        from jax.sharding import PartitionSpec as P

        return jax.jit(
            jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=P(self.axis),
                out_specs=P(*out_parts),
                check_vma=False,
            )
        )

    # -- staging --------------------------------------------------------------

    def _row_devices(self, rank: int):
        """Devices forming rank's row of the mesh (1 for the ici mesh)."""
        devs = np.asarray(self.mesh.devices)
        axis_pos = self.mesh.axis_names.index(self.axis)
        row = np.moveaxis(devs, axis_pos, 0)[rank]
        return list(np.atleast_1d(row).flat)

    def _cache_get(self, arr, rank: int):
        key = (id(arr), rank)
        ent = self._staged.get(key)
        if ent is not None:
            ref, staged = ent
            if ref() is arr:
                self._staged.move_to_end(key)
                self.stats["stage_hits"] += 1
                return staged
            del self._staged[key]
        return None

    def _cache_put(self, arr, rank: int, staged) -> None:
        import weakref

        try:
            ref = weakref.ref(arr)
        except TypeError:
            return  # not weakref-able (e.g. plain list): skip caching
        self._staged[(id(arr), rank)] = (ref, staged)
        while len(self._staged) > _STAGE_CACHE_CAP:
            self._staged.popitem(last=False)

    def invalidate(self, arr) -> None:
        """Drop any staged copies of `arr` (call after in-place mutation)."""
        for key in [k for k in self._staged if k[0] == id(arr)]:
            self._staged.pop(key, None)

    def _is_staged(self, arr) -> bool:
        import jax

        return (
            isinstance(arr, jax.Array)
            and arr.ndim >= 1
            and arr.shape[0] == self.world
            and arr.sharding == self._sharding(self.axis)
        )

    def stage_local(self, arr, rank: int, cache: bool = True):
        """Stage THIS rank's contribution into the global (world,)+S array.

        Multi-controller: only this process's addressable row is filled;
        peers stage their own rows and the runtime stitches the global view.
        Device-resident jax.Arrays already carrying the staged sharding pass
        through untouched.
        """
        import jax

        if self._is_staged(arr):
            return arr
        if cache:
            hit = self._cache_get(arr, rank)
            if hit is not None:
                return hit
        local = np.asarray(arr)
        global_shape = (self.world,) + local.shape
        sharding = self._sharding(self.axis)
        row = set(self._row_devices(rank))
        # Multi-controller: only this rank's row is addressable, so exactly
        # the local payload is copied. Single-controller: the sharding spans
        # every device, so the other rows are zero-filled (the reduce
        # identity for the psum/ppermute paths that consume stage_local).
        zeros = None
        shards, copied = [], 0
        for d in sharding.addressable_devices:
            if d in row:
                shards.append(jax.device_put(local[None], d))
                copied += local.nbytes
            else:
                if zeros is None:
                    zeros = np.zeros((1,) + local.shape, local.dtype)
                shards.append(jax.device_put(zeros, d))
        staged = jax.make_array_from_single_device_arrays(
            global_shape, sharding, shards
        )
        self.stats["stage_misses"] += 1
        self.stats["staged_bytes"] += copied
        if cache:
            self._cache_put(arr, rank, staged)
        return staged

    def stage_parts(self, parts: Sequence[Any], cache_token=None):
        """Single-controller staging: one contribution per rank (tests and
        benchmarks drive all `world` ranks from one process)."""
        import jax

        if len(parts) != self.world:
            raise ValueError(
                f"stage_parts wants {self.world} rank contributions, "
                f"got {len(parts)}"
            )
        if cache_token is not None:
            hit = self._cache_get(cache_token, -1)
            if hit is not None:
                return hit
        rows = [np.asarray(p) for p in parts]
        shards = []
        for rank, row in enumerate(rows):
            for d in self._row_devices(rank):
                shards.append(jax.device_put(row[None], d))
        global_shape = (self.world,) + rows[0].shape
        staged = jax.make_array_from_single_device_arrays(
            global_shape, self._sharding(self.axis), shards
        )
        self.stats["stage_misses"] += 1
        self.stats["staged_bytes"] += sum(r.nbytes for r in rows)
        if cache_token is not None:
            self._cache_put(cache_token, -1, staged)
        return staged

    def rank_shard(self, garr, rank: int) -> np.ndarray:
        """Host copy of rank's block of a P(axis)-sharded result."""
        block = garr.shape[0] // self.world
        for s in garr.addressable_shards:
            idx = s.index[0]
            start = 0 if idx.start is None else idx.start
            if start == rank * block:
                return np.asarray(s.data)
        raise ValueError(
            f"rank {rank}'s shard is not addressable from this process"
        )

    # -- compiled ops ---------------------------------------------------------

    def _timed(self, op: str, garr, fn):
        t0 = time.perf_counter()
        out = fn(garr)
        out.block_until_ready()
        _observe(
            op,
            self.group_name,
            garr.nbytes // max(self.world, 1),
            time.perf_counter() - t0,
        )
        return out

    def allreduce(self, garr, op: str = SUM):
        """(world,)+S staged -> replicated S."""
        import jax
        import jax.numpy as jnp

        axis = self.axis
        key = ("allreduce", op, garr.shape, str(garr.dtype))

        def build():
            if op == SUM:
                body = lambda x: jax.lax.psum(jnp.squeeze(x, 0), axis)
            elif op == MAX:
                body = lambda x: jax.lax.pmax(jnp.squeeze(x, 0), axis)
            elif op == MIN:
                body = lambda x: jax.lax.pmin(jnp.squeeze(x, 0), axis)
            elif op == PRODUCT:
                # no pprod primitive: gather the rank dimension and reduce
                body = lambda x: jnp.prod(
                    jax.lax.all_gather(jnp.squeeze(x, 0), axis, axis=0),
                    axis=0,
                )
            else:
                raise ValueError(f"unknown reduce op {op!r}")
            return self._smap(body, ())

        return self._timed("allreduce", garr, self._program(key, build))

    def allgather(self, garr):
        """(world,)+S staged -> replicated (world,)+S. Each rank stages only
        its own shard; the gather happens inside the compiled program (no
        world× host allocation anywhere)."""
        import jax
        import jax.numpy as jnp

        axis = self.axis
        key = ("allgather", garr.shape, str(garr.dtype))

        def build():
            body = lambda x: jax.lax.all_gather(
                jnp.squeeze(x, 0), axis, axis=0
            )
            return self._smap(body, ())

        return self._timed("allgather", garr, self._program(key, build))

    def reducescatter(self, garr, op: str = SUM):
        """(world,)+T staged (full tensor per rank, T[0] divisible by world)
        -> P(axis) global T; rank i's block is rank_shard(out, i)."""
        import jax
        import jax.numpy as jnp

        axis, world = self.axis, self.world
        if garr.shape[1] % world != 0:
            raise ValueError(
                f"reducescatter needs dim0 {garr.shape[1]} divisible by "
                f"world {world}"
            )
        key = ("reducescatter", op, garr.shape, str(garr.dtype))

        def build():
            if op == SUM:
                body = lambda x: jax.lax.psum_scatter(
                    jnp.squeeze(x, 0), axis, scatter_dimension=0, tiled=True
                )
            else:
                block = garr.shape[1] // world

                def body(x):
                    v = jnp.squeeze(x, 0)
                    if op == MAX:
                        red = jax.lax.pmax(v, axis)
                    elif op == MIN:
                        red = jax.lax.pmin(v, axis)
                    elif op == PRODUCT:
                        red = jnp.prod(
                            jax.lax.all_gather(v, axis, axis=0), axis=0
                        )
                    else:
                        raise ValueError(f"unknown reduce op {op!r}")
                    idx = jax.lax.axis_index(axis)
                    return jax.lax.dynamic_slice_in_dim(
                        red, idx * block, block
                    )

            return self._smap(body, (axis,))

        return self._timed("reducescatter", garr, self._program(key, build))

    def broadcast(self, garr, src: int):
        """(world,)+S staged -> P(axis) (world,)+S where every row is src's.

        jax.lax.ppermute forbids duplicate sources, so one-to-many runs as a
        doubling tree: round r moves the value from the 2^r ranks that hold
        it to the next 2^r (log2(world) ppermute hops — on TPU each is one
        ICI traversal, exactly how XLA lowers collective-broadcast)."""
        import jax
        import jax.numpy as jnp

        axis, world = self.axis, self.world
        key = ("broadcast", src, garr.shape, str(garr.dtype))

        def build():
            def body(x):
                v = x  # keep the (1,)+S block so out P(axis) re-stacks rows
                idx = jax.lax.axis_index(axis)
                t = (idx - src) % world  # shifted rank: src is t=0
                span = 1
                while span < world:
                    perm = [
                        ((u + src) % world, (u + span + src) % world)
                        for u in range(span)
                        if u + span < world
                    ]
                    moved = jax.lax.ppermute(v, axis, perm=perm)
                    recv = (t >= span) & (t < 2 * span)
                    v = jnp.where(recv, moved, v)
                    span *= 2
                return v

            return self._smap(body, (axis,))

        return self._timed("broadcast", garr, self._program(key, build))

    def permute(self, garr, perm: Sequence[Tuple[int, int]]):
        """(world,)+S staged -> P(axis) (world,)+S: row dst takes row src for
        each (src, dst) pair; rows that are no pair's destination get zeros.
        This is the send/recv and compiled-channel payload hop."""
        import jax

        axis = self.axis
        perm = tuple((int(s), int(d)) for s, d in perm)
        key = ("permute", perm, garr.shape, str(garr.dtype))

        def build():
            # no squeeze: the (1,)+S block shape survives the hop so the
            # P(axis) output re-stacks to (world,)+S
            body = lambda x: jax.lax.ppermute(x, axis, perm=list(perm))
            return self._smap(body, (axis,))

        return self._timed("permute", garr, self._program(key, build))

    def barrier(self) -> None:
        """All ranks rendezvous inside one tiny compiled psum."""
        import jax

        if self._barrier_input is None:
            if jax.process_count() > 1:
                self._barrier_input = self.stage_local(
                    np.ones(1, dtype=np.float32), jax.process_index()
                )
            else:
                self._barrier_input = self.stage_parts(
                    [np.ones(1, dtype=np.float32)] * self.world
                )
        out = self.allreduce(self._barrier_input, SUM)
        out.block_until_ready()

    # -- mesh-rebased attention (parallel/ring_attention.py, ulysses.py) ------

    def _stage_seq(self, x, seq_dim: int = 1):
        """Stage a [B, T, H, D]-style array sequence-sharded over the group
        axis. Single-controller: x is the global array. Multi-controller: x
        is this process's local sequence shard."""
        import jax

        sharding_parts = [None] * np.asarray(x).ndim
        sharding_parts[seq_dim] = self.axis
        sharding = self._sharding(*sharding_parts)
        if jax.process_count() <= 1:
            return jax.device_put(np.asarray(x), sharding)
        local = np.asarray(x)
        rank = jax.process_index()
        global_shape = list(local.shape)
        global_shape[seq_dim] = local.shape[seq_dim] * self.world
        shards = [jax.device_put(local, d) for d in self._row_devices(rank)]
        return jax.make_array_from_single_device_arrays(
            tuple(global_shape), sharding, shards
        )

    def ring_attention(self, q, k, v, causal: bool = False):
        """Ring attention over the group mesh with the group's compiled
        program cache (parallel/ring_attention.py rebased onto the engine:
        same kernel, but the shard_map program is built once per
        (shape, dtype, causal) instead of re-traced per call)."""
        import functools

        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.ring_attention import ring_attention as _ring

        qs = self._stage_seq(q)
        ks = self._stage_seq(k)
        vs = self._stage_seq(v)
        key = ("ring_attention", qs.shape, ks.shape, str(qs.dtype), causal)

        def build():
            import jax

            fn = functools.partial(
                _ring,
                axis_name=self.axis,
                axis_size=self.world,
                causal=causal,
                pvary_axes=(self.axis,),
            )
            spec = P(None, self.axis, None, None)
            return jax.jit(
                jax.shard_map(
                    fn,
                    mesh=self.mesh,
                    in_specs=(spec, spec, spec),
                    out_specs=spec,
                    check_vma=False,
                )
            )

        t0 = time.perf_counter()
        out = self._program(key, build)(qs, ks, vs)
        out.block_until_ready()
        _observe(
            "ring_attention",
            self.group_name,
            qs.nbytes // max(self.world, 1),
            time.perf_counter() - t0,
        )
        return out

    def ulysses_attention(self, q, k, v, causal: bool = False):
        """Ulysses all-to-all attention over the group mesh, compiled and
        cached like ring_attention. Heads must divide by world."""
        import functools

        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.ulysses import ulysses_attention as _ulysses

        qs = self._stage_seq(q)
        ks = self._stage_seq(k)
        vs = self._stage_seq(v)
        key = ("ulysses", qs.shape, ks.shape, str(qs.dtype), causal)

        def build():
            import jax

            fn = functools.partial(
                _ulysses, axis_name=self.axis, causal=causal
            )
            spec = P(None, self.axis, None, None)
            return jax.jit(
                jax.shard_map(
                    fn,
                    mesh=self.mesh,
                    in_specs=(spec, spec, spec),
                    out_specs=spec,
                    check_vma=False,
                )
            )

        t0 = time.perf_counter()
        out = self._program(key, build)(qs, ks, vs)
        out.block_until_ready()
        _observe(
            "ulysses_attention",
            self.group_name,
            qs.nbytes // max(self.world, 1),
            time.perf_counter() - t0,
        )
        return out
