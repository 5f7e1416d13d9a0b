"""Array-native channels for compiled DAGs + the ICI device-to-device path.

Analog of python/ray/experimental/channel/torch_tensor_nccl_channel.py: the
reference moves GPU tensors between compiled-DAG actors over NCCL, skipping
the object store and host memory. The TPU-native translation has two layers:

1. ``TensorChannel`` — a shm channel specialized for jax/numpy arrays: raw
   dtype/shape header + buffer memcpy instead of cloudpickle (which both
   copies and byte-stuffs). Cross-actor, same-host.

2. ``DeviceTensorChannel`` — the compiled-graph device channel: the shm slot
   carries only a CONTROL FRAME (magic + dtype + shape), and the payload hops
   device-to-device through a cached compiled ``ppermute`` program
   (ray_tpu.util.collective.mesh_ops.MeshCollectives over a 2-device
   submesh). On TPU hardware the transfer rides ICI links without touching
   host memory; both DAG actors join the one SPMD program, exactly as both
   ranks participate in the reference's NCCL send/recv. Wire format and mode
   selection: docs/collectives.md.

3. ``make_ici_transfer`` — the minimal building block underneath (2): a
   jitted shard_map ppermute hop over a live Mesh, kept as the unit-testable
   primitive.
"""

from __future__ import annotations

import struct
from functools import partial
from typing import Any

import numpy as np

from ray_tpu.dag.channel import DATA_OFFSET, HEADER, Channel, ChannelFullError

_MAGIC_ARRAY = 0xA1
_MAGIC_ARRAY_OK = 0xA2  # array wrapped in the exec-loop ("ok", value) tuple
_MAGIC_PICKLE = 0xB2
_MAGIC_DEVICE = 0xD1  # control frame: payload hopped device->device
_MAGIC_DEVICE_OK = 0xD2  # device frame wrapped in ("ok", value)
# [magic: u8][ndim: u8][dtype-len: u8][reserved: u8][nbytes: u64]
_AHDR = struct.Struct("<BBBxQ")

# Loopback handoff: when one process addresses both endpoint devices (CPU
# sim, or a DAG pinned to one TPU host) the hopped dst shard is parked here
# by channel name for the same-process reader — the device array never
# leaves the device. Cross-process readers fall back to the frame body.
_DEVICE_SLOTS: dict = {}


class TensorChannel(Channel):
    """One-slot shm channel whose array payloads skip pickle entirely.

    Synchronization is inherited from Channel (seqlock read loop + decode
    hook); only the payload encoding differs.
    """

    # -- writer side ---------------------------------------------------------

    def write(self, value: Any) -> None:
        magic = _MAGIC_ARRAY
        if (
            type(value) is tuple
            and len(value) == 2
            and isinstance(value[0], str)
            and value[0] == "ok"
        ):
            # Exec-loop wire tuple: keep the array fast path for the value.
            magic = _MAGIC_ARRAY_OK
            value = value[1]
        arr = self._as_array(value)
        if arr is None or arr.dtype.hasobject:
            payload = _pickle_payload(
                ("ok", value) if magic == _MAGIC_ARRAY_OK else value
            )
            self._write_raw(_MAGIC_PICKLE, payload, b"", ())
            return
        shape = arr.shape  # BEFORE ascontiguousarray (it promotes 0-d to 1-d)
        arr = np.ascontiguousarray(arr)
        self._write_raw(
            magic, arr.view(np.uint8).reshape(-1), arr.dtype.str.encode(), shape
        )

    @staticmethod
    def _as_array(value: Any):
        if isinstance(value, np.ndarray):
            return value
        t = type(value)
        if t.__module__.startswith("jax") or t.__name__ == "ArrayImpl":
            import jax

            return np.asarray(jax.device_get(value))
        return None

    def _write_raw(self, magic: int, body, dtype_b: bytes, shape) -> None:
        shape_b = b"".join(struct.pack("<q", d) for d in shape)
        nbytes = body.nbytes if isinstance(body, np.ndarray) else len(body)
        total = _AHDR.size + len(dtype_b) + len(shape_b) + nbytes
        if total > self.max_buf_size:
            raise ChannelFullError(
                f"message of {total} bytes exceeds channel capacity "
                f"{self.max_buf_size}; recompile with a larger max_buf_size"
            )
        view = self._seg.view
        seq, _ = HEADER.unpack_from(view, 0)
        HEADER.pack_into(view, 0, seq + 1, total)  # odd = writing
        off = DATA_OFFSET
        _AHDR.pack_into(view, off, magic, len(shape), len(dtype_b), nbytes)
        off += _AHDR.size
        view[off : off + len(dtype_b)] = dtype_b
        off += len(dtype_b)
        view[off : off + len(shape_b)] = shape_b
        off += len(shape_b)
        if isinstance(body, np.ndarray):
            np.frombuffer(view, dtype=np.uint8, count=nbytes, offset=off)[:] = body
        else:
            view[off : off + nbytes] = body
        HEADER.pack_into(view, 0, seq + 2, total)  # even = sealed

    # -- reader side ---------------------------------------------------------

    def _decode_payload(self, payload: bytes) -> Any:
        """Parse a validated snapshot (Channel.read's seqlock already copied
        it out of the slot, so no extra array copy is needed here)."""
        magic, ndim, dlen, nbytes = _AHDR.unpack_from(payload, 0)
        off = _AHDR.size
        dtype_b = payload[off : off + dlen]
        off += dlen
        shape = tuple(
            struct.unpack_from("<q", payload, off + 8 * i)[0] for i in range(ndim)
        )
        off += 8 * ndim
        if magic == _MAGIC_PICKLE:
            import cloudpickle

            return cloudpickle.loads(payload[off : off + nbytes])
        data = np.frombuffer(payload, dtype=np.uint8, count=nbytes, offset=off)
        out = data.view(np.dtype(dtype_b.decode())).reshape(shape)
        return ("ok", out) if magic == _MAGIC_ARRAY_OK else out


class DeviceTensorChannel(TensorChannel):
    """Compiled-graph device channel: shm control frame + ppermute payload.

    ``meta`` names the producer/consumer collective ranks:
    ``{"group": <collective group>, "src": <rank>, "dst": <rank>}``.
    The first array write resolves one of three modes (docs/collectives.md):

    - ``ici``: multi-controller jax (the named xla collective group spans
      processes). The slot carries only [magic, dtype, shape]; the payload
      moves through a cached compiled ppermute over the 2-device
      (src, dst) submesh of the group's ici mesh — writer stages its shard,
      reader joins the same SPMD program with a zeros contribution and keeps
      the hopped dst shard. No host memory, no object store.
    - ``loopback``: one process addresses both devices (CPU sim / one-host
      DAG). The hop still runs — the dst-device array is parked in
      ``_DEVICE_SLOTS`` for a same-process reader — and the frame also
      carries the raw bytes so a cross-process reader on the same host
      degrades to the TensorChannel path instead of deadlocking.
    - ``shm``: no usable device pair; plain TensorChannel behavior.

    Non-array values (STOP sentinel, errors, pickled results) always take
    the inherited shm path, so DAG teardown and error propagation are
    identical across modes.
    """

    def __init__(self, name: str, max_buf_size: int = 10 * 1024 * 1024, *,
                 create: bool = False, meta=None):
        super().__init__(name, max_buf_size, create=create)
        meta = meta or {}
        self.group_name = meta.get("group", "default")
        self.src = int(meta.get("src", 0))
        self.dst = int(meta.get("dst", 1))
        self._mode = None
        self._engine = None

    # -- mode + engine resolution --------------------------------------------

    def _resolve(self):
        if self._mode is not None:
            return self._mode
        try:
            import jax

            from ray_tpu.util.collective import collective as _col
            from ray_tpu.util.collective.mesh_ops import MeshCollectives
            from jax.sharding import Mesh

            group = None
            if _col.is_group_initialized(self.group_name):
                group = _col._manager.get(self.group_name)
            if (
                group is not None
                and group.engine is not None
                and group.world_size > max(self.src, self.dst)
                and jax.process_count() > 1
            ):
                ici = group.engine.mesh
                devs = np.asarray(
                    [ici.devices.flat[self.src], ici.devices.flat[self.dst]]
                )
                self._engine = MeshCollectives(
                    Mesh(devs, ("chan",)), axis="chan",
                    group_name=f"chan:{self.group_name}",
                )
                self._mode = "ici"
            elif (
                jax.process_count() == 1
                and self.src != self.dst
                and len(jax.devices()) > max(self.src, self.dst)
            ):
                devs = np.asarray(
                    [jax.devices()[self.src], jax.devices()[self.dst]]
                )
                self._engine = MeshCollectives(
                    Mesh(devs, ("chan",)), axis="chan",
                    group_name=f"chan:{self.group_name}",
                )
                self._mode = "loopback"
            else:
                self._mode = "shm"
        except Exception:
            self._mode = "shm"
        return self._mode

    # -- writer side ---------------------------------------------------------

    def write(self, value: Any) -> None:
        magic = _MAGIC_DEVICE
        if (
            type(value) is tuple
            and len(value) == 2
            and isinstance(value[0], str)
            and value[0] == "ok"
        ):
            magic = _MAGIC_DEVICE_OK
            value = value[1]
        arr = self._device_array(value)
        if arr is None or self._resolve() == "shm":
            # shm mode / non-array payloads: inherited TensorChannel wire.
            restored = (
                ("ok", value) if magic == _MAGIC_DEVICE_OK else value
            )
            super().write(restored)
            return
        shape = tuple(arr.shape)
        dtype_b = np.dtype(arr.dtype).str.encode()
        hopped = self._engine.permute(
            self._engine.stage_local(arr, 0, cache=False), [(0, 1)]
        )
        if self._mode == "ici":
            # Control frame only; the payload lives on the dst device. The
            # frame seals AFTER the hop completes so a reader that sees it
            # can immediately consume the shard.
            self._write_raw(magic, b"", dtype_b, shape)
            return
        # loopback: park the dst-device shard for a same-process reader and
        # ALSO carry the bytes so a cross-process reader still decodes.
        for s in hopped.addressable_shards:
            start = s.index[0].start or 0
            if start == 1:
                _DEVICE_SLOTS[self.name] = s.data.reshape(shape)
                break
        host = np.ascontiguousarray(np.asarray(value))
        self._write_raw(
            magic, host.view(np.uint8).reshape(-1), dtype_b, shape
        )

    @staticmethod
    def _device_array(value):
        """Arrays eligible for the device hop (numpy is staged; jax.Array
        single-device payloads pass through)."""
        if isinstance(value, np.ndarray) and not value.dtype.hasobject:
            return value
        t = type(value)
        if t.__module__.startswith("jax") or t.__name__ == "ArrayImpl":
            return value
        return None

    # -- reader side ---------------------------------------------------------

    def _decode_payload(self, payload: bytes) -> Any:
        magic, ndim, dlen, nbytes = _AHDR.unpack_from(payload, 0)
        if magic not in (_MAGIC_DEVICE, _MAGIC_DEVICE_OK):
            return super()._decode_payload(payload)
        off = _AHDR.size
        dtype = np.dtype(payload[off : off + dlen].decode())
        off += dlen
        shape = tuple(
            struct.unpack_from("<q", payload, off + 8 * i)[0]
            for i in range(ndim)
        )
        off += 8 * ndim
        mode = self._resolve()
        if mode == "loopback" or self._mode == "loopback":
            slot = _DEVICE_SLOTS.pop(self.name, None)
            if slot is not None:
                out = slot
            else:
                # Cross-process reader on the same host: frame body carries
                # the bytes (TensorChannel degradation).
                data = np.frombuffer(
                    payload, dtype=np.uint8, count=nbytes, offset=off
                )
                out = data.view(dtype).reshape(shape)
        elif mode == "ici":
            # Join the writer's SPMD hop with a zeros contribution; keep the
            # shard that landed on our (dst) device.
            zeros = np.zeros(shape, dtype)
            hopped = self._engine.permute(
                self._engine.stage_local(zeros, 1, cache=False), [(0, 1)]
            )
            out = None
            for s in hopped.addressable_shards:
                if (s.index[0].start or 0) == 1:
                    out = s.data.reshape(shape)
                    break
            if out is None:
                raise RuntimeError(
                    f"device channel {self.name}: dst shard not addressable"
                )
        else:
            raise RuntimeError(
                f"device channel {self.name}: control frame received but no "
                f"device path is available in this process (group "
                f"{self.group_name!r} not initialized?)"
            )
        return ("ok", out) if magic == _MAGIC_DEVICE_OK else out


def _pickle_payload(value) -> bytes:
    import pickle

    import cloudpickle

    return cloudpickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)


def make_ici_transfer(mesh, axis: str, src: int, dst: int):
    """Compile a device-to-device shard transfer over a live mesh.

    Returns a jitted fn moving the ``src`` device's shard of ``x`` onto the
    ``dst`` device's shard slot via one ppermute hop — on TPU this is one
    ICI link traversal with no host round trip (reference analog: NCCL
    send/recv between aDAG actors). Other shards pass through unchanged.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _hop(x):
        moved = jax.lax.ppermute(x, axis, perm=[(src, dst)])
        idx = jax.lax.axis_index(axis)
        # dst's slot takes the moved shard; everyone else keeps their own.
        return jax.numpy.where(idx == dst, moved, x)

    return jax.jit(_hop)
