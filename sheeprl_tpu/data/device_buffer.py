"""Accelerator-resident sequential replay buffer.

The reference keeps replay in host RAM (numpy / memmap,
``sheeprl/data/buffers.py:363-743``) and re-stages every sampled batch to the
accelerator: at replay ratio 0.5 each stored frame crosses the host→device
link ~16 times over its lifetime (batch 16 × seq 64 resamples). On TPU the
natural layout is the opposite — the ring lives in HBM, each env step uploads
its ~KB-sized transition exactly once, and sequence sampling is an on-chip
gather (HBM→HBM at memory bandwidth, no host link traffic at all): the
dominant per-update transfer (megabytes of pixels) becomes a few kilobytes
of gather indices.

Semantics mirror ``EnvIndependentReplayBuffer(buffer_cls=SequentialReplayBuffer)``
(per-env ring cursors, contiguous windows that never straddle an env's write
cursor, multinomial env split per batch — ``data/buffers.py:308-527``), so the
Dreamer-family loops can swap buffers without touching their math. Index
drawing stays on the host (the host mirrors the cursors; drawing needs no
device data), only the draw result crosses the link.

Storage layout: the ring is stored in the form its programs address, so no
program relayouts it (:class:`RingArrays`). A ``uint8`` key of ``n`` bytes
an item is ``uint8[n_envs, capacity + 1, ceil(n / 128), 128]`` — whole
lane-dense rows, the last one zero-padded: one frame is one contiguous run
(64 x 64 x 3 is ``[96, 128]``, three 32 x 128 tiles, no padding). Every other
key is a column range of one packed ``float32[n_envs, capacity + 1, width]``
array, ``width`` rounded up to whole 128-lane rows: the row ``add`` stages
them as. The indexed dimensions (env, slot) are untiled major dimensions and
the tiled ones are always taken whole: a write is one
``dynamic_update_slice`` a local env and array, under a donated ``jit`` that
aliases the ring in place, and a gather reads whole rows and restores the
items' shapes on the gathered batch only. (An item whose minor dimension is
3 has no good tiled layout on the chip: stored as ``[..., 64, 64, 3]`` the
runtime kept the slots minor-most, and every write and gather copied the
whole ring to reach the layout it was emitted for.) The extra slot at
``capacity`` is a scratch row that absorbs writes of envs excluded from a
partial ``add``, so one compiled program serves full and partial adds alike.
Outside the ring nothing shows: ``host_arrays``, pickles and the host-buffer
conversions keep ``[n_envs, capacity, *item]`` arrays.

On a pure data-parallel mesh the ring shards along the env axis
(``NamedSharding`` over ``data_axis``, ``n_envs`` divisible by the axis
size): every device owns a contiguous block of env rows, ``add`` writes
each device's env slice into its own shard under ``shard_map`` (per-device
cursor arithmetic, no cross-device traffic), and the pure sampling kernels
run shard-locally at fixed shapes — both from the host paths (gathers come
out batch-sharded, ready for the data-parallel train step) and from inside
a fused superstep's scan (each device draws its own batch shard).
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.obs.span import span
from sheeprl_tpu.parallel.shard_map import shard_map


#: lanes of a TPU tile: the minor dimension of every stored array is a whole
#: multiple of it
_LANES = 128


def _lane_rows(n: int) -> int:
    return -(-int(n) // _LANES)


class RingLayout(NamedTuple):
    """What the stored arrays hold, read from the first ``add`` (static under
    ``jit``: it rides as the aux data of :class:`RingArrays`)."""

    #: ``(key, item shape)`` of the ``uint8`` keys
    pixels: Tuple[Tuple[str, Tuple[int, ...]], ...]
    #: ``(key, first column, end column, item shape)`` of the ``float32`` keys
    smalls: Tuple[Tuple[str, int, int, Tuple[int, ...]], ...]

    @classmethod
    def of(cls, arrays: Dict[str, np.ndarray]) -> "RingLayout":
        """From ``[a, b, *item]`` arrays a key (a step dict or a checkpoint's
        ``[E, cap, *item]``); keys in sorted order."""
        pixels, smalls, offset = [], [], 0
        for k in sorted(arrays):
            v = arrays[k]
            item = tuple(int(d) for d in v.shape[2:])
            if v.dtype == np.uint8:
                pixels.append((k, item))
            else:
                width = int(np.prod(item))
                smalls.append((k, offset, offset + width, item))
                offset += width
        return cls(tuple(pixels), tuple(smalls))

    @property
    def keys(self) -> Tuple[str, ...]:
        return tuple(k for k, _ in self.pixels) + tuple(s[0] for s in self.smalls)

    @property
    def columns(self) -> Dict[str, Tuple[int, int]]:
        """``key -> (first column, end column)`` in the packed array."""
        return {k: (o0, o1) for k, o0, o1, _ in self.smalls}

    @property
    def small_width(self) -> int:
        """Columns of the packed array: the keys' widths, in whole lane rows."""
        return _lane_rows(self.smalls[-1][2] if self.smalls else 0) * _LANES

    def stored_shapes(self, *lead: int) -> Tuple[Dict[str, Tuple[int, ...]], Tuple[int, ...]]:
        """Shapes of the stored form under the leading dimensions ``lead``."""
        pixels = {k: (*lead, _lane_rows(np.prod(item)), _LANES) for k, item in self.pixels}
        return pixels, (*lead, self.small_width)

    def store(self, arrays: Dict[str, np.ndarray], pixels: Dict[str, np.ndarray], smalls: np.ndarray, at: tuple) -> None:
        """Write ``[*lead, *item]`` arrays into host arrays of the stored
        form, at the leading index ``at`` (ints and slices: the targets are
        views, filled through a flat view of their rows)."""
        rows = smalls[at]
        lead = rows.shape[:-1]
        for k, o0, o1, _ in self.smalls:
            rows[..., o0:o1] = np.asarray(arrays[k]).reshape(*lead, -1)
        for k, item in self.pixels:
            flat = pixels[k].reshape(*pixels[k].shape[:-2], -1)[at]
            flat[..., : int(np.prod(item))] = np.asarray(arrays[k]).reshape(*lead, -1)

    def restore(self, pixels: Dict[str, Any], smalls: Any) -> Dict[str, Any]:
        """The items' own shapes back on arrays of the stored form, numpy or
        jax, whatever their leading dimensions: the one inverse of the
        storage rule (every gather and ``host_arrays`` end here)."""
        lead = smalls.shape[:-1]
        out = {}
        for k, item in self.pixels:
            out[k] = pixels[k].reshape(*lead, -1)[..., : int(np.prod(item))].reshape(*lead, *item)
        for k, o0, o1, item in self.smalls:
            out[k] = smalls[..., o0:o1].reshape(*lead, *item)
        return out


@jax.tree_util.register_pytree_node_class
class RingArrays:
    """The ring as its programs address it: ``pixels[k]`` is
    ``uint8[n_envs, capacity + 1, rows, 128]``, ``smalls`` the packed
    ``float32[n_envs, capacity + 1, width]``. A pytree whose static part is
    the :class:`RingLayout`, so the items' shapes travel with the arrays
    through ``jit`` and ``shard_map`` into the in-graph draws."""

    def __init__(self, pixels: Dict[str, jax.Array], smalls: jax.Array, layout: RingLayout) -> None:
        self.pixels = pixels
        self.smalls = smalls
        self.layout = layout

    def tree_flatten(self):
        return (self.pixels, self.smalls), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(*children, layout)

    @property
    def capacity(self) -> int:
        # static under jit: the trailing scratch slot is excluded from sampling
        return self.smalls.shape[1] - 1

    def rows(self, env_idx: jax.Array, time_idx: jax.Array) -> Dict[str, jax.Array]:
        """HBM→HBM gather of the slots ``(env_idx, time_idx)`` (broadcast
        against each other): only the two leading dimensions are indexed,
        and the items' shapes come back on the gathered batch."""
        return self.layout.restore(
            {k: b[env_idx, time_idx] for k, b in self.pixels.items()}, self.smalls[env_idx, time_idx]
        )


# --------------------------------------------------------------------------- #
# Pure sampling kernels.
#
# Everything below is a plain function of device arrays — callable from inside
# another jitted program (the fused training supersteps scan these to draw a
# fresh replay batch per gradient step without a host round trip) as well as
# from the buffer's own jitted methods. ``bufs`` is the :class:`RingArrays`
# (slot ``capacity`` is the partial-add scratch row and is never sampled);
# validity is recomputed on device from the two tiny cursor arrays
# ``pos``/``full``, so the mask shapes are fixed and nothing recompiles as
# the ring fills.
# --------------------------------------------------------------------------- #


def sequence_start_mask(
    pos: jax.Array, full: jax.Array, capacity: int, span: int
) -> jax.Array:
    """``[n_envs, capacity]`` bool mask of valid sequence-window starts — the
    on-device mirror of :meth:`DeviceReplayBuffer._valid_starts` (windows of
    ``span`` steps that do not straddle the env's write cursor)."""
    s = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    pos = jnp.asarray(pos, jnp.int32)[:, None]
    full = jnp.asarray(full, bool)[:, None]
    first_end = pos - span + 1
    second_end = jnp.where(first_end >= 0, capacity, capacity + first_end)
    when_full = (s < jnp.maximum(first_end, 0)) | ((s >= pos) & (s < second_end))
    return jnp.where(full, when_full, s < first_end)


def transition_item_mask(
    pos: jax.Array, full: jax.Array, capacity: int, sample_next_obs: bool
) -> jax.Array:
    """``[n_envs, capacity]`` bool mask of valid transition items — the
    on-device mirror of :meth:`DeviceReplayBuffer._valid_items` (when
    ``sample_next_obs`` the slot before the cursor is excluded too: its
    successor is the oldest slot, about to be overwritten)."""
    s = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    pos = jnp.asarray(pos, jnp.int32)[:, None]
    full = jnp.asarray(full, bool)[:, None]
    end = pos - (1 if sample_next_obs else 0)
    second_end = jnp.where(end >= 0, capacity, capacity + end)
    when_full = (s < jnp.maximum(end, 0)) | ((s >= pos) & (s < second_end))
    return jnp.where(full, when_full, s < jnp.maximum(end, 0))


def draw_from_mask(key: jax.Array, mask: jax.Array, n: int) -> Tuple[jax.Array, jax.Array]:
    """Draw ``(env_idx [n], item [n])`` from a validity mask with the stock
    sampling distribution — uniform env, then uniform over that env's valid
    entries — on a jax RNG stream (the host paths use the buffer's numpy
    generator; the streams differ, the distribution matches). Every env must
    have at least one valid entry (the callers validate on host before
    dispatch)."""
    n_envs = mask.shape[0]
    k_env, k_item = jax.random.split(key)
    env_idx = jax.random.randint(k_env, (n,), 0, n_envs, dtype=jnp.int32)
    rows = mask[env_idx].astype(jnp.int32)  # [n, capacity]
    counts = rows.sum(axis=1)
    u = jax.random.uniform(k_item, (n,))
    j = jnp.minimum((u * counts.astype(jnp.float32)).astype(jnp.int32), jnp.maximum(counts - 1, 0))
    # item = the (j+1)-th True of the env's row: uniform over valid entries
    item = jnp.argmax(jnp.cumsum(rows, axis=1) > j[:, None], axis=1)
    return env_idx, item.astype(jnp.int32)


def gather_sequences(bufs: RingArrays, env_idx: jax.Array, time_idx: jax.Array) -> Dict[str, jax.Array]:
    """HBM→HBM sequence gather: ``env_idx [B]``, ``time_idx [B, T]`` →
    ``[T, B, ...]`` values (time-major, the layout the fused train steps
    consume)."""
    return {k: jnp.swapaxes(g, 0, 1) for k, g in bufs.rows(env_idx[:, None], time_idx).items()}


def gather_transitions(
    bufs: RingArrays,
    env_idx: jax.Array,
    time_idx: jax.Array,
    next_idx: Optional[jax.Array] = None,
    obs_keys: Sequence[str] = (),
) -> Dict[str, jax.Array]:
    """Transition gather: ``env_idx``/``time_idx [...]`` → ``[..., *item]``,
    with ``next_<k>`` of the ``obs_keys`` read at ``next_idx`` when given."""
    out = bufs.rows(env_idx, time_idx)
    if next_idx is not None:
        nxt = bufs.rows(env_idx, next_idx)
        out.update({f"next_{k}": nxt[k] for k in obs_keys if k in nxt})
    return out


def draw_sequence_batch(
    bufs: RingArrays,
    pos: jax.Array,
    full: jax.Array,
    key: jax.Array,
    batch_size: int,
    sequence_length: int,
) -> Dict[str, jax.Array]:
    """One ``[T, B, ...]`` sequence batch drawn and gathered entirely
    in-graph — the Dreamer-family replay read of a fused superstep."""
    capacity = bufs.capacity
    mask = sequence_start_mask(pos, full, capacity, sequence_length)
    env_idx, starts = draw_from_mask(key, mask, batch_size)
    offsets = jnp.arange(sequence_length, dtype=jnp.int32)
    time_idx = (starts[:, None] + offsets[None, :]) % capacity
    return gather_sequences(bufs, env_idx, time_idx)


def draw_transition_batch(
    bufs: RingArrays,
    pos: jax.Array,
    full: jax.Array,
    key: jax.Array,
    batch_size: int,
    sample_next_obs: bool = False,
    obs_keys: Sequence[str] = (),
) -> Dict[str, jax.Array]:
    """One ``[B, ...]`` uniform-transition batch drawn and gathered entirely
    in-graph — the SAC-family replay read of a fused superstep. Matches the
    :meth:`DeviceReplayBuffer.sample_transitions` output contract
    (``next_<k>`` at item+1 when ``sample_next_obs``)."""
    capacity = bufs.capacity
    mask = transition_item_mask(pos, full, capacity, sample_next_obs)
    env_idx, items = draw_from_mask(key, mask, batch_size)
    return gather_transitions(
        bufs, env_idx, items, (items + 1) % capacity if sample_next_obs else None, obs_keys
    )


class DeviceReplayBuffer:
    """Sequential replay ring resident on an accelerator device.

    Drop-in for the ``EnvIndependentReplayBuffer``/``SequentialReplayBuffer``
    pair in single-process training loops: same ``add`` signature
    (``[1, n, ...]`` step dicts, optional env ``indices``), same sampling
    distribution, but ``sample_batches`` yields device-resident
    ``[T, B, ...]`` batches gathered on-chip.

    Pass ``mesh``/``data_axis`` (a pure data-parallel mesh; ``n_envs``
    divisible by the axis size) to shard the ring along the env axis: each
    device owns ``n_envs / shards`` contiguous env rows, writes and gathers
    run shard-locally under ``shard_map``, batches come out sharded along
    the batch axis, and the env draw becomes stratified — exactly
    ``batch / shards`` samples per device block, uniform within the block
    (the per-env marginal stays uniform; batch sizes must divide by the
    shard count). :meth:`superstep_inputs` then hands a fused superstep a
    context it can consume under the same sharding with zero resharding.
    """

    def __init__(
        self,
        buffer_size: int,
        n_envs: int = 1,
        obs_keys: Sequence[str] = ("observations",),
        device: Optional[jax.Device] = None,
        seed: Optional[int] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        data_axis: Optional[str] = None,
    ) -> None:
        if buffer_size <= 0:
            raise ValueError(f"The buffer size must be greater than zero, got: {buffer_size}")
        if n_envs <= 0:
            raise ValueError(f"The number of environments must be greater than zero, got: {n_envs}")
        self._buffer_size = int(buffer_size)
        self._n_envs = int(n_envs)
        self._obs_keys = tuple(obs_keys)
        self._device = device
        self._mesh = None
        self._data_axis = None
        self._n_shards = 1
        self._sharding: Optional[NamedSharding] = None
        if mesh is not None and data_axis is not None and int(mesh.shape[data_axis]) > 1:
            shards = int(mesh.shape[data_axis])
            if device is not None:
                raise ValueError("pass either 'device' or 'mesh'/'data_axis', not both")
            if n_envs % shards:
                raise ValueError(
                    f"a sharded ring needs n_envs ({n_envs}) divisible by the "
                    f"'{data_axis}' mesh axis size ({shards})"
                )
            self._mesh = mesh
            self._data_axis = data_axis
            self._n_shards = shards
            self._sharding = NamedSharding(mesh, P(data_axis))
        self._rng = np.random.default_rng(seed)
        # host mirrors of the per-env ring cursors (the device never needs
        # to report them back)
        self._pos = np.zeros((n_envs,), np.int64)
        self._full = np.zeros((n_envs,), bool)
        self._bufs: Optional[RingArrays] = None
        self._pending_arrays: Optional[Dict[str, np.ndarray]] = None
        self._write = None
        self._gather = None
        self._amend = None

    # ------------------------------------------------------------- properties
    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def full(self) -> Sequence[bool]:
        return tuple(bool(f) for f in self._full)

    @property
    def empty(self) -> Sequence[bool]:
        return tuple(not f and p == 0 for f, p in zip(self._full, self._pos))

    @property
    def is_memmap(self) -> Sequence[bool]:
        return tuple(False for _ in range(self._n_envs))

    @property
    def device(self) -> Optional[jax.Device]:
        return self._device

    def devices(self) -> List[str]:
        """``platform:id`` of every device holding a piece of the ring
        (empty until the first ``add`` allocates it)."""
        from sheeprl_tpu.parallel.fabric import tree_devices

        return tree_devices(self._bufs)

    @property
    def sharded(self) -> bool:
        return self._n_shards > 1

    @property
    def n_shards(self) -> int:
        return self._n_shards

    def __len__(self) -> int:
        return self._buffer_size

    def __repr__(self) -> str:
        # the placement clause is load-bearing for debuggability: tests and
        # bug reports assert the ring landed where the resolver said it would
        if self.sharded:
            placement = (
                f"placement=sharded(axis={self._data_axis!r}, shards={self._n_shards}, "
                f"envs_per_shard={self._n_envs // self._n_shards})"
            )
        else:
            dev = self._device if self._device is not None else "default"
            placement = f"placement=single({dev})"
        return (
            f"DeviceReplayBuffer(buffer_size={self._buffer_size}, n_envs={self._n_envs}, "
            f"allocated={self._bufs is not None}, {placement})"
        )

    # ------------------------------------------------------------- allocation
    def _allocate(self, layout: RingLayout, arrays: Optional[Dict[str, np.ndarray]] = None) -> None:
        """Put the ring on its device(s) in the stored form: zeros, or the
        ``[E, cap, *item]`` ``arrays`` of a checkpoint or a host buffer."""
        lead = (self._n_envs, self._buffer_size + 1)
        pixel_shapes, small_shape = layout.stored_shapes(*lead)
        placement = self._sharding or self._device
        if arrays is None:
            pixels = {k: jnp.zeros(shape, jnp.uint8, device=placement) for k, shape in pixel_shapes.items()}
            smalls = jnp.zeros(small_shape, jnp.float32, device=placement)
        else:
            pixels = {k: np.zeros(shape, np.uint8) for k, shape in pixel_shapes.items()}
            smalls = np.zeros(small_shape, np.float32)
            layout.store(arrays, pixels, smalls, (slice(None), slice(0, self._buffer_size)))
            pixels, smalls = jax.device_put((pixels, smalls), placement)
        self._bufs = RingArrays(pixels, smalls, layout)
        # staging arrays of one step, in the stored form: allocated once and
        # overwritten in place by every add() through the flat views
        stage_shapes, stage_small = layout.stored_shapes(self._n_envs)
        self._stage_pos = np.empty((self._n_envs,), np.int32)
        self._stage_smalls = np.zeros(stage_small, np.float32)
        self._stage_pixels = {k: np.zeros(shape, np.uint8) for k, shape in stage_shapes.items()}
        self._build_kernels(layout)

    def _build_kernels(self, layout: RingLayout) -> None:
        # Each jitted program carries a stable name of its own (the XLA
        # module is ``jit_<name>``), so a device trace says which program an
        # op belongs to: ``ring_write``, ``ring_amend``,
        # ``ring_set_truncated``, ``ring_gather_sequences``,
        # ``ring_gather_transitions`` and ``ring_gather_transitions_next``
        # (howto/telemetry.md lists them).
        #
        # under shard_map every operand arrives as its per-device block, so
        # the kernels index with the LOCAL env count — per-device cursor
        # arithmetic falls out of the same code that serves the 1-device ring
        n_envs = self._n_envs // self._n_shards
        capacity = self._buffer_size
        columns = layout.columns
        obs_keys = self._obs_keys
        ax = self._data_axis

        def ring_write(bufs, pixels, smalls, pos):
            # one row a local env and array, updated in place: the form XLA
            # reaches by itself for a one-env scatter
            out, packed = dict(bufs.pixels), bufs.smalls
            for e in range(n_envs):
                for k in out:
                    out[k] = lax.dynamic_update_slice(out[k], pixels[k][e][None, None], (e, pos[e], 0, 0))
                packed = lax.dynamic_update_slice(packed, smalls[e][None, None], (e, pos[e], 0))
            return RingArrays(out, packed, layout)

        def ring_gather_sequences(bufs, env_idx, time_idx):
            return gather_sequences(bufs, env_idx, time_idx)

        def ring_gather_transitions(bufs, env_idx, time_idx):
            return gather_transitions(bufs, env_idx, time_idx)

        def ring_gather_transitions_next(bufs, env_idx, time_idx, next_idx):
            return gather_transitions(bufs, env_idx, time_idx, next_idx, obs_keys)

        def ring_amend(bufs, env_i, slot, terminated, truncated, is_first):
            if ax is not None:
                # the env's row lives in one device's block; the others send
                # their copy of the patch to the scratch slot
                env_i = env_i - lax.axis_index(ax) * n_envs
                slot = jnp.where((env_i >= 0) & (env_i < n_envs), slot, capacity)
                env_i = jnp.clip(env_i, 0, n_envs - 1)
            pixels, packed = bufs.pixels, bufs.smalls
            for k, v in (("terminated", terminated), ("truncated", truncated), ("is_first", is_first)):
                if k in columns:
                    o0, o1 = columns[k]
                    packed = lax.dynamic_update_slice(
                        packed, jnp.full((1, 1, o1 - o0), v, packed.dtype), (env_i, slot, o0)
                    )
            return RingArrays(pixels, packed, layout)

        def ring_set_truncated(bufs, slots, values):
            o0, _ = columns["truncated"]
            pixels, packed = bufs.pixels, bufs.smalls
            for e in range(n_envs):
                packed = lax.dynamic_update_slice(packed, values[e][None, None], (e, slots[e], o0))
            return RingArrays(pixels, packed, layout)

        if self.sharded:
            mesh = self._mesh
            # write: every operand (ring, staging arrays, cursor vector) is
            # env-axis sharded, so each device updates its own env block —
            # no collective appears in the program
            ring_write = shard_map(ring_write, mesh, in_specs=(P(ax), P(ax), P(ax), P(ax)), out_specs=P(ax))
            ring_set_truncated = shard_map(ring_set_truncated, mesh, in_specs=(P(ax), P(ax), P(ax)), out_specs=P(ax))
            ring_amend = shard_map(ring_amend, mesh, in_specs=(P(ax), P(), P(), P(), P(), P()), out_specs=P(ax))
            # host-path gathers: the draw is stratified per shard (see
            # draw_indices), index arrays arrive batch-axis sharded with
            # SHARD-LOCAL env ids, and the batch comes out pre-sharded along
            # the batch axis — exactly the layout the data-parallel train
            # step consumes
            ring_gather_sequences = shard_map(
                ring_gather_sequences, mesh, in_specs=(P(ax), P(ax), P(ax)), out_specs=P(None, ax)
            )
            ring_gather_transitions = shard_map(
                ring_gather_transitions,
                mesh,
                in_specs=(P(ax), P(None, ax), P(None, ax)),
                out_specs=P(None, ax),
            )
            ring_gather_transitions_next = shard_map(
                ring_gather_transitions_next,
                mesh,
                in_specs=(P(ax), P(None, ax), P(None, ax), P(None, ax)),
                out_specs=P(None, ax),
            )

        # writes donate the ring: XLA aliases the update in place
        self._write = jax.jit(ring_write, donate_argnums=0)
        # amend is the rare failure-recovery patch path (one env, one slot),
        # set_truncated the checkpoint's flag fix-up (every env's last slot)
        self._amend = jax.jit(ring_amend, donate_argnums=0)
        self._set_truncated = jax.jit(ring_set_truncated, donate_argnums=0)
        # the gathers wrap the module-level pure kernels (also callable from
        # inside a fused superstep's scan body), jitted here for the host paths
        self._gather = jax.jit(ring_gather_sequences)
        self._gather_transitions = jax.jit(ring_gather_transitions)
        self._gather_transitions_next = jax.jit(ring_gather_transitions_next)

    # ------------------------------------------------------------------ write
    def add(
        self,
        data: Dict[str, np.ndarray],
        indices: Optional[Sequence[int]] = None,
        validate_args: bool = False,
    ) -> None:
        """Append one time step for the given envs (all envs when ``indices``
        is None). ``data`` values are ``[1, len(indices), ...]`` host arrays —
        the same step-dict contract as ``EnvIndependentReplayBuffer.add``."""
        if not isinstance(data, dict):
            raise ValueError(f"'data' must be a dictionary, got {type(data)}")
        first = np.asarray(next(iter(data.values())))
        if first.shape[0] != 1:
            raise ValueError(
                f"DeviceReplayBuffer.add stores one step per call; got a [{first.shape[0]}, ...] block"
            )
        if indices is None:
            indices = range(self._n_envs)
        indices = list(indices)
        if validate_args and len(indices) != first.shape[1]:
            raise ValueError(
                f"The length of 'indices' ({len(indices)}) must be equal to the second dimension of the "
                f"arrays in 'data' ({first.shape[1]})"
            )
        if self._bufs is None:
            self._allocate(RingLayout.of({k: np.asarray(v) for k, v in data.items()}))
        layout = self._bufs.layout
        if set(data) != set(layout.keys):
            raise ValueError(
                f"add() keys {sorted(data)} do not match the allocated keys {sorted(layout.keys)}"
            )

        # write targets: the env's cursor, or the scratch slot for envs not
        # in this (partial) add. Rows of envs excluded from a partial add
        # keep stale bytes, which land harmlessly in the scratch slot
        pos, pixels, smalls = self._stage_pos, self._stage_pixels, self._stage_smalls
        pos.fill(self._buffer_size)
        for col, env in enumerate(indices):
            pos[env] = self._pos[env]
            layout.store({k: v[0, col] for k, v in data.items()}, pixels, smalls, (env,))

        ref_device = (
            self._mesh.devices.flat[0] if self._mesh is not None else (self._device or jax.devices()[0])
        )
        if ref_device.platform == "cpu":
            # PJRT CPU device_put may alias aligned numpy buffers zero-copy;
            # the staging arrays are refilled on the next add() while the
            # donated write may still be queued — hand the transfer copies
            pixels = {k: v.copy() for k, v in pixels.items()}
            smalls = smalls.copy()
            pos = pos.copy()
        # on a sharded ring the staging arrays are env-major too, so one
        # sharded device_put scatters each device's env slice onto its shard
        dev_args = jax.device_put((pixels, smalls, jnp.asarray(pos)), self._sharding or self._device)
        self._bufs = self._write(self._bufs, *dev_args)
        for env in indices:
            self._pos[env] += 1
            if self._pos[env] >= self._buffer_size:
                self._pos[env] = 0
                self._full[env] = True

    def amend_last(self, env_idx: int, terminated: float, truncated: float, is_first: float) -> None:
        """Rewrite the done/first flags of the most recent step of one env —
        the failure-recovery patch path (``RestartOnException`` buffer fixup,
        reference ``dreamer_v3.py:591-604``)."""
        if self._bufs is None:
            return
        slot = int((self._pos[env_idx] - 1) % self._buffer_size)
        self._bufs = self._amend(
            self._bufs,
            jnp.int32(env_idx),
            jnp.int32(slot),
            jnp.float32(terminated),
            jnp.float32(truncated),
            jnp.float32(is_first),
        )

    # ----------------------------------------------------------------- sample
    def _draw_env_idx(self, n: int) -> np.ndarray:
        """Env split of a host-side draw. Single-device: uniform over envs
        (multinomial counts, the stock distribution). Sharded: stratified —
        batch block ``s`` draws uniformly from shard ``s``'s env rows, so the
        gathered batch partitions cleanly along the batch axis (fixed
        per-shard sample counts; the per-env marginal stays uniform because
        every shard owns the same number of envs)."""
        if not self.sharded:
            return self._rng.integers(0, self._n_envs, (n,), dtype=np.intp)
        if n % self._n_shards:
            raise ValueError(
                f"a sharded ring draws fixed per-shard batch blocks: batch size "
                f"({n}) must divide by the shard count ({self._n_shards})"
            )
        n_local = self._n_envs // self._n_shards
        block = np.repeat(np.arange(self._n_shards, dtype=np.intp), n // self._n_shards)
        return block * n_local + self._rng.integers(0, n_local, (n,), dtype=np.intp)

    def _valid_starts(self, env: int, span: int) -> np.ndarray:
        """Window starts for one env that do not straddle its write cursor —
        the same validity rule as ``SequentialReplayBuffer.sample``
        (``data/buffers.py:341-354``)."""
        pos = int(self._pos[env])
        if self._full[env]:
            first_end = pos - span + 1
            second_end = self._buffer_size if first_end >= 0 else self._buffer_size + first_end
            return np.concatenate(
                [np.arange(0, max(first_end, 0)), np.arange(pos, second_end)]
            ).astype(np.intp)
        if pos - span + 1 < 1:
            return np.empty((0,), np.intp)
        return np.arange(0, pos - span + 1, dtype=np.intp)

    def draw_indices(
        self, batch_size: int, sequence_length: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Draw ``(env_idx [B], start [B])`` with the stock sampling
        distribution: multinomial env split, then uniform over each env's
        valid windows."""
        if batch_size <= 0:
            raise ValueError(f"'batch_size' ({batch_size}) must be greater than 0")
        if self._bufs is None:
            raise RuntimeError("The buffer has not been initialized. Try to add some data first.")
        env_idx = self._draw_env_idx(batch_size)
        starts = np.empty((batch_size,), np.intp)
        for env in np.unique(env_idx):
            valid = self._valid_starts(int(env), sequence_length)
            if len(valid) == 0:
                raise ValueError(
                    f"Cannot sample a sequence of length {sequence_length} from env {env}. "
                    f"Data added so far: {self._pos[env]}"
                )
            rows = np.nonzero(env_idx == env)[0]
            starts[rows] = valid[self._rng.integers(0, len(valid), size=(len(rows),), dtype=np.intp)]
        return env_idx, starts

    def sample_batches(
        self, batch_size: int, sequence_length: int, n_samples: int
    ) -> Iterator[Dict[str, jax.Array]]:
        """Yield ``n_samples`` device-resident ``[T, B, ...]`` batches.

        Per batch, only ``B * (T + 1)`` int32 indices cross the host→device
        link; the pixel bytes move HBM→HBM inside one jitted gather."""
        if n_samples <= 0:
            raise ValueError(f"'n_samples' ({n_samples}) must be greater than 0")
        offsets = np.arange(sequence_length, dtype=np.int64)
        for _ in range(n_samples):
            # host time only: index draw, device_put, dispatch of the gather
            # (the gather's device time is ``ring_gather_sequences`` in a trace)
            with span("replay/draw"):
                env_idx, starts = self.draw_indices(batch_size, sequence_length)
                time_idx = (starts[:, None] + offsets[None, :]) % self._buffer_size
                if self.sharded:
                    # the sharded gather indexes each device's env block, so the
                    # (per-block stratified) env ids are rebased shard-locally
                    env_idx = env_idx % (self._n_envs // self._n_shards)
                ei, ti = jax.device_put(
                    (env_idx.astype(np.int32), time_idx.astype(np.int32)),
                    self._sharding or self._device,
                )
                batch = self._gather(self._bufs, ei, ti)
            yield batch

    # ------------------------------------------------- transition sampling
    def _valid_items(self, env: int, sample_next_obs: bool) -> np.ndarray:
        """Item indices of one env whose (transition) does not straddle its
        write cursor — the per-env mirror of ``ReplayBuffer._valid_idxes``
        (``data/buffers.py:189-214``): when ``sample_next_obs`` the slot just
        before the cursor is excluded too (its successor is the oldest slot,
        about to be overwritten)."""
        pos = int(self._pos[env])
        end = pos - 1 if sample_next_obs else pos
        if self._full[env]:
            second_end = self._buffer_size if end >= 0 else self._buffer_size + end
            return np.concatenate(
                [np.arange(0, max(end, 0)), np.arange(pos, second_end)]
            ).astype(np.intp)
        return np.arange(0, max(end, 0), dtype=np.intp)

    def sample_transitions(
        self,
        batch_size: int,
        n_samples: int = 1,
        sample_next_obs: bool = False,
    ) -> Dict[str, jax.Array]:
        """Uniform transition sample, shape ``[n_samples, batch_size, ...]``,
        device-resident — the SAC-family counterpart of ``sample_batches``:
        same output contract as host ``ReplayBuffer.sample`` (uniform env,
        uniform valid item, ``next_<k>`` at item+1 when ``sample_next_obs``),
        but only the int32 indices cross the host→device link; the batch
        bytes move HBM→HBM inside one jitted gather."""
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(
                f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0"
            )
        if self._bufs is None:
            raise RuntimeError("The buffer has not been initialized. Try to add some data first.")
        n = batch_size * n_samples
        if self.sharded:
            # stratify each sample row independently so every [batch] row
            # partitions into equal per-shard blocks (see _draw_env_idx)
            env_idx = np.concatenate([self._draw_env_idx(batch_size) for _ in range(n_samples)])
        else:
            env_idx = self._rng.integers(0, self._n_envs, (n,), dtype=np.intp)
        items = np.empty((n,), np.intp)
        for env in np.unique(env_idx):
            valid = self._valid_items(int(env), sample_next_obs)
            if len(valid) == 0:
                # ValueError to match the host ReplayBuffer contract for
                # empty/insufficient data (buffers.py raises ValueError there
                # and RuntimeError only for the uninitialized ring) so
                # buffer-mode-swapping callers catch one exception type
                raise ValueError(
                    "You want to sample the next observations, but not enough samples have been "
                    f"added to env {env}. Make sure that at least two samples are added."
                    if sample_next_obs
                    else "No sample has been added to the buffer. Please add at least one sample "
                    "calling 'self.add()'"
                )
            rows = np.nonzero(env_idx == env)[0]
            items[rows] = valid[self._rng.integers(0, len(valid), size=(len(rows),), dtype=np.intp)]
        if self.sharded:
            # 2-D [n_samples, batch] indices (shard-local env ids), sharded
            # along the batch axis: the gather returns the final
            # [n_samples, batch, ...] layout pre-sharded — no on-device
            # reshape of a sharded axis
            row_spec = NamedSharding(self._mesh, P(None, self._data_axis))
            shape2 = (n_samples, batch_size)
            env_local = (env_idx % (self._n_envs // self._n_shards)).astype(np.int32)
            ei, ti = jax.device_put(
                (env_local.reshape(shape2), items.astype(np.int32).reshape(shape2)), row_spec
            )
            if sample_next_obs:
                ni = jax.device_put(
                    ((items + 1) % self._buffer_size).astype(np.int32).reshape(shape2), row_spec
                )
                return self._gather_transitions_next(self._bufs, ei, ti, ni)
            return self._gather_transitions(self._bufs, ei, ti)
        ei, ti = jax.device_put(
            (env_idx.astype(np.int32), items.astype(np.int32)), self._device
        )
        if sample_next_obs:
            ni = jax.device_put(((items + 1) % self._buffer_size).astype(np.int32), self._device)
            flat = self._gather_transitions_next(self._bufs, ei, ti, ni)
        else:
            flat = self._gather_transitions(self._bufs, ei, ti)
        return {k: v.reshape(n_samples, batch_size, *v.shape[1:]) for k, v in flat.items()}

    def superstep_inputs(
        self,
        sequence_length: Optional[int] = None,
        sample_next_obs: bool = False,
    ) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
        """Operands for an in-graph replay draw: ``(bufs, pos, full)``.

        A fused training superstep closes over :func:`draw_sequence_batch`
        / :func:`draw_transition_batch` and receives these as its (static
        for the window) sample context — only the two ``[n_envs]`` cursor
        arrays cross the host→device link per train window. Validity is
        checked on the host here, with the same errors as
        :meth:`draw_indices` / :meth:`sample_transitions`, because the
        in-graph draw cannot raise. Pass ``sequence_length`` for sequence
        sampling, leave it ``None`` for transition sampling. The ring must
        not be written between this call and the dispatched superstep —
        train windows never interleave with env steps, so the loops satisfy
        this by construction."""
        if self._bufs is None:
            raise RuntimeError("The buffer has not been initialized. Try to add some data first.")
        # host time only: the validity check and the cursors' device_put
        with span("replay/draw"):
            for env in range(self._n_envs):
                if sequence_length is not None:
                    if len(self._valid_starts(env, int(sequence_length))) == 0:
                        raise ValueError(
                            f"Cannot sample a sequence of length {sequence_length} from env {env}. "
                            f"Data added so far: {self._pos[env]}"
                        )
                elif len(self._valid_items(env, sample_next_obs)) == 0:
                    raise ValueError(
                        "You want to sample the next observations, but not enough samples have been "
                        f"added to env {env}. Make sure that at least two samples are added."
                        if sample_next_obs
                        else "No sample has been added to the buffer. Please add at least one sample "
                        "calling 'self.add()'"
                    )
            # copies: on CPU device_put may alias the host mirrors zero-copy, and
            # add() mutates them in place while the superstep is still queued.
            # On a sharded ring the cursors land env-axis sharded like the bufs,
            # so the superstep's shard_map hands each device its own cursor block
            pos, full = jax.device_put(
                (self._pos.astype(np.int32), self._full.copy()), self._sharding or self._device
            )
        return self._bufs, pos, full

    def flag_last_truncated(self) -> Optional[np.ndarray]:
        """Set ``truncated=1`` on every env's most recent step (checkpoint
        self-consistency — reference ``callback.py:87-142``) and return the
        clobbered values for :meth:`restore_last_truncated`."""
        if self._bufs is None or "truncated" not in self._bufs.layout.columns:
            return None
        o0, o1 = self._bufs.layout.columns["truncated"]
        slots = ((self._pos - 1) % self._buffer_size).astype(np.int32)
        saved = np.asarray(jax.device_get(self._bufs.smalls[np.arange(self._n_envs), slots, o0:o1]))
        self._write_truncated(slots, np.ones_like(saved))
        return saved

    def restore_last_truncated(self, saved: Optional[np.ndarray]) -> None:
        if saved is None or self._bufs is None:
            return
        self._write_truncated(((self._pos - 1) % self._buffer_size).astype(np.int32), saved)

    def _write_truncated(self, slots: np.ndarray, values: np.ndarray) -> None:
        args = jax.device_put((slots, values.astype(np.float32)), self._sharding or self._device)
        self._bufs = self._set_truncated(self._bufs, *args)

    # ------------------------------------------------------------- checkpoint
    def host_arrays(self) -> Dict[str, np.ndarray]:
        """Fetch the ring (without the scratch slot) as ``[E, cap, ...]``
        numpy arrays — one bulk transfer per stored array."""
        if self._bufs is None:
            return dict(self._pending_arrays or {})
        pixels, smalls = jax.device_get((self._bufs.pixels, self._bufs.smalls))
        filled = slice(0, self._buffer_size)
        return self._bufs.layout.restore({k: v[:, filled] for k, v in pixels.items()}, smalls[:, filled])

    def __getstate__(self) -> Dict[str, Any]:
        arrays = self.host_arrays()
        layout = RingLayout.of(arrays)
        # the external format of every checkpoint since the ring exists:
        # ``[E, cap, *item]`` arrays and the three key tables beside them
        state = {
            "buffer_size": self._buffer_size,
            "n_envs": self._n_envs,
            "obs_keys": self._obs_keys,
            "rng": self._rng,
            "pos": self._pos,
            "full": self._full,
            "small_slices": {k: (o0, o1, item) for k, o0, o1, item in layout.smalls},
            "small_keys": tuple(s[0] for s in layout.smalls),
            "pixel_keys": tuple(k for k, _ in layout.pixels),
            "arrays": arrays,
        }
        return state

    def __setstate__(self, state: Dict[str, Any]) -> None:
        self._buffer_size = state["buffer_size"]
        self._n_envs = state["n_envs"]
        self._obs_keys = tuple(state["obs_keys"])
        self._rng = state["rng"]
        self._pos = state["pos"]
        self._full = state["full"]
        self._device = None  # re-pinned by the restoring process
        # meshes do not pickle: a restored ring comes back single-device and
        # the restoring run's jitted consumers reshard it on first use
        self._mesh = None
        self._data_axis = None
        self._n_shards = 1
        self._sharding = None
        self._bufs = None
        self._write = self._gather = self._amend = None
        # the stored form is rebuilt from the arrays alone (restore_to_device)
        self._pending_arrays = state["arrays"]

    def restore_to_device(self, device: Optional[jax.Device] = None) -> "DeviceReplayBuffer":
        """Upload a restored (unpickled) ring back to ``device``."""
        self._device = device
        arrays = getattr(self, "_pending_arrays", None)
        if arrays:
            self._allocate(RingLayout.of(arrays), arrays)
            self._pending_arrays = None
        return self

    @classmethod
    def from_host_buffer(
        cls, host_rb: Any, device: Optional[jax.Device] = None, seed: Optional[int] = None
    ) -> "DeviceReplayBuffer":
        """Bulk-load an ``EnvIndependentReplayBuffer`` (e.g. from a resumed
        checkpoint) into HBM."""
        subs = host_rb.buffer
        n_envs = len(subs)
        out = cls(host_rb.buffer_size, n_envs=n_envs, obs_keys=subs[0]._obs_keys, device=device, seed=seed)
        keys = list(subs[0].buffer.keys())
        arrays = {
            k: np.stack([np.asarray(sub.buffer[k])[:, 0] for sub in subs]) for k in keys
        }
        out._pos = np.array([sub._pos for sub in subs], np.int64)
        out._full = np.array([sub.full for sub in subs], bool)
        # _pending_arrays carries [E, cap, ...]; reuse the restore path
        out._pending_arrays = arrays
        out.restore_to_device(device)
        return out

    @classmethod
    def from_transition_host_buffer(
        cls, host_rb: Any, device: Optional[jax.Device] = None, seed: Optional[int] = None
    ) -> "DeviceReplayBuffer":
        """Bulk-load a plain ``ReplayBuffer`` (SAC-family checkpoint,
        ``[size, n_envs, ...]`` arrays with one global cursor) into HBM."""
        arrays = {k: np.asarray(v).swapaxes(0, 1) for k, v in host_rb.buffer.items()}
        out = cls(
            host_rb.buffer_size,
            n_envs=host_rb.n_envs,
            obs_keys=host_rb._obs_keys,
            device=device,
            seed=seed,
        )
        out._pos = np.full((host_rb.n_envs,), host_rb._pos, np.int64)
        out._full = np.full((host_rb.n_envs,), host_rb.full, bool)
        out._pending_arrays = arrays
        out.restore_to_device(device)
        return out

    def to_transition_host_buffer(self, memmap: bool = False, memmap_dir: Any = None) -> Any:
        """Materialize as a stock plain ``ReplayBuffer`` (the SAC-family host
        layout) — the cursors advance in lockstep in those loops, so env 0's
        cursor is the global one."""
        from sheeprl_tpu.data.buffers import ReplayBuffer

        host = ReplayBuffer(
            self._buffer_size,
            n_envs=self._n_envs,
            obs_keys=self._obs_keys,
            memmap=memmap,
            memmap_dir=memmap_dir,
        )
        if not ((self._pos == self._pos[0]).all() and (self._full == self._full[0]).all()):
            raise RuntimeError(
                "to_transition_host_buffer requires lockstep env cursors (the plain "
                f"ReplayBuffer has one global cursor) but pos={self._pos.tolist()} "
                f"full={self._full.tolist()} — this ring was written with partial "
                "per-env adds; convert with to_host_buffer() instead"
            )
        arrays = self.host_arrays()
        host.add({k: v.swapaxes(0, 1) for k, v in arrays.items()})
        host._pos = int(self._pos[0])
        host._full = bool(self._full[0])
        return host

    def ring_bytes(self) -> int:
        """Current HBM footprint of the allocated ring."""
        return sum(v.nbytes for v in jax.tree.leaves(self._bufs))

    def to_host_buffer(self, memmap: bool = False, memmap_dir: Any = None) -> Any:
        """Materialize as a stock ``EnvIndependentReplayBuffer`` (host RAM),
        e.g. to hand a checkpoint to a host-buffer run."""
        from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer

        host = EnvIndependentReplayBuffer(
            self._buffer_size,
            n_envs=self._n_envs,
            obs_keys=self._obs_keys,
            memmap=memmap,
            memmap_dir=memmap_dir,
            buffer_cls=SequentialReplayBuffer,
        )
        arrays = self.host_arrays()
        for env, sub in enumerate(host.buffer):
            # prime allocation with a single step, then overwrite wholesale
            step = {k: v[env : env + 1, 0:1].swapaxes(0, 1) for k, v in arrays.items()}
            sub.add(step)
            for k, v in arrays.items():
                sub[k] = v[env][:, None]
            sub._pos = int(self._pos[env])
            sub._full = bool(self._full[env])
        return host


def lower_ring_programs(
    step: Dict[str, Any],
    buffer_size: int,
    n_envs: int,
    batch_size: int,
    sequence_length: int,
    device: Optional[jax.Device] = None,
) -> Dict[str, Any]:
    """``ring_write`` and ``ring_gather_sequences`` lowered from shapes alone
    (``step``: ``[1, n_envs, *item]`` shapes with dtypes, as ``add`` takes
    them), so that a ring of any size can be compiled and its optimised HLO
    and ``memory_analysis()`` read with nothing allocated. ``device`` may be
    a described one (``jax.experimental.topologies``)."""
    sharding = jax.sharding.SingleDeviceSharding(device) if device is not None else None

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rb = DeviceReplayBuffer(buffer_size, n_envs=n_envs)
    layout = RingLayout.of(step)
    rb._build_kernels(layout)
    pixel_shapes, small_shape = layout.stored_shapes(n_envs, buffer_size + 1)
    ring = RingArrays({k: sds(v, jnp.uint8) for k, v in pixel_shapes.items()}, sds(small_shape, jnp.float32), layout)
    stage_shapes, stage_small = layout.stored_shapes(n_envs)
    staged = ({k: sds(v, jnp.uint8) for k, v in stage_shapes.items()}, sds(stage_small, jnp.float32))
    return {
        "ring_write": rb._write.lower(ring, *staged, sds((n_envs,), jnp.int32)),
        "ring_gather_sequences": rb._gather.lower(
            ring, sds((batch_size,), jnp.int32), sds((batch_size, sequence_length), jnp.int32)
        ),
    }


def _stored_step_bytes(spaces: Sequence[Any], copies: int, extra_floats: int) -> int:
    """Bytes of one slot in the stored form: each ``uint8`` space in whole
    128-byte rows, everything else as columns of the packed ``float32``
    array, itself in whole 128-lane rows (``copies`` of every space)."""
    pixel_bytes = sum(_lane_rows(np.prod(sp.shape)) * _LANES for sp in spaces if np.issubdtype(sp.dtype, np.uint8))
    floats = sum(int(np.prod(sp.shape)) for sp in spaces if not np.issubdtype(sp.dtype, np.uint8))
    return copies * pixel_bytes + _lane_rows(copies * floats + extra_floats) * _LANES * 4


def estimate_ring_bytes(
    obs_space: Any, actions_dim: Sequence[int], buffer_size: int, n_envs: int
) -> int:
    """Upper-bound estimate of the HBM ring footprint for a Dreamer-style
    step dict (obs keys + actions + 4 scalar flags), padding included, used
    by the ``auto`` device-buffer decision before any data exists."""
    spaces = [obs_space[k] for k in obs_space.spaces]
    return _stored_step_bytes(spaces, 1, int(np.sum(actions_dim)) + 4) * int(buffer_size) * int(n_envs)


def estimate_transition_bytes(
    obs_space: Any,
    keys: Sequence[str],
    actions_dim: Sequence[int],
    buffer_size: int,
    n_envs: int,
    store_next_obs: bool,
) -> int:
    """Upper-bound HBM estimate for a SAC-style transition step dict: the
    stored obs keys (doubled when the loop stores explicit next obs), actions
    and 3 scalar flags, padding included."""
    spaces = [obs_space[k] for k in keys]
    copies = 2 if store_next_obs else 1
    return _stored_step_bytes(spaces, copies, int(np.sum(actions_dim)) + 3) * int(buffer_size) * int(n_envs)


def resolve_device_buffer(
    cfg: Any,
    fabric: Any,
    obs_space: Any,
    actions_dim: Sequence[int],
    buffer_size: int,
    n_envs: int,
    estimated_bytes: Optional[int] = None,
) -> bool:
    """Decide whether this run keeps replay in HBM.

    The ring has two placements: single-device, and sharded along the env
    axis of a pure data-parallel mesh. ``buffer.device=true`` forces HBM and
    raises when neither placement fits (multi-process runs, ``model_axis``
    meshes, or ``n_envs`` not divisible by the data-axis size); ``auto``
    picks HBM when a placement fits AND the backend is not CPU AND the
    estimated footprint stays under ``buffer.device_max_bytes`` (on a
    sharded ring that budget is per the whole mesh — each device holds
    ``1/data_parallel_size`` of it).
    """
    from sheeprl_tpu.obs.telemetry import telemetry_resolved

    spec = cfg.buffer.get("device", "auto")
    unsupported_reason = None
    if fabric.num_processes != 1:
        unsupported_reason = (
            f"the ring cannot span processes (num_processes={fabric.num_processes})"
        )
    elif fabric.world_size > 1 and fabric.model_axis is not None:
        unsupported_reason = (
            f"the sharded ring needs a pure data-parallel mesh, but this run "
            f"shards params over model_axis={fabric.model_axis!r}"
        )
    elif fabric.world_size > 1 and n_envs % fabric.data_parallel_size:
        unsupported_reason = (
            f"the sharded ring splits env rows evenly across the data axis, but "
            f"n_envs={n_envs} does not divide by data_parallel_size={fabric.data_parallel_size}"
        )
    if spec in (True, "true", "True"):
        if unsupported_reason is not None:
            raise ValueError(f"buffer.device=true is impossible here: {unsupported_reason}")
        on_device, why = True, "forced"
    elif spec in (False, "false", "False", None):
        on_device, why = False, "forced"
    elif spec != "auto":
        raise ValueError(f"unknown buffer.device spec {spec!r}; use auto/true/false")
    elif unsupported_reason is not None:
        on_device, why = False, unsupported_reason
    elif jax.default_backend() == "cpu":
        on_device, why = False, "the default backend is the host"
    else:
        est = (
            estimated_bytes
            if estimated_bytes is not None
            else estimate_ring_bytes(obs_space, actions_dim, buffer_size, n_envs)
        )
        budget = int(cfg.buffer.get("device_max_bytes", 8_000_000_000))
        on_device, why = est <= budget, f"estimated {est} bytes against buffer.device_max_bytes={budget}"
    telemetry_resolved("buffer_device", "device" if on_device else "host", spec=str(spec), why=why)
    return on_device


def _mesh_kwargs(fabric: Any) -> Dict[str, Any]:
    """Constructor kwargs that place the ring on ``fabric``'s mesh: the
    env-axis sharding on a (>1 device) pure data-parallel mesh, single-device
    otherwise — :func:`resolve_device_buffer` has already rejected every
    topology the ring cannot serve."""
    if fabric.world_size > 1:
        return {"mesh": fabric.mesh, "data_axis": fabric.data_axis}
    return {}


def make_sequential_replay(
    cfg: Any,
    fabric: Any,
    obs_space: Any,
    actions_dim: Sequence[int],
    buffer_size: int,
    num_envs: int,
    obs_keys: Sequence[str],
    memmap_dir: Any,
    seed: Optional[int],
) -> Any:
    """Construct the sequential replay for a Dreamer-family loop: the HBM
    ring when :func:`resolve_device_buffer` allows it, else the stock
    host ``EnvIndependentReplayBuffer``."""
    from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer

    if resolve_device_buffer(cfg, fabric, obs_space, actions_dim, buffer_size, num_envs):
        rb = DeviceReplayBuffer(
            buffer_size,
            n_envs=num_envs,
            obs_keys=obs_keys,
            seed=seed,
            **(_mesh_kwargs(fabric)),
        )
        assert ("sharded" in repr(rb)) == (fabric.world_size > 1), repr(rb)
        return rb
    return EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=memmap_dir,
        buffer_cls=SequentialReplayBuffer,
        seed=seed,
    )


def make_transition_replay(
    cfg: Any,
    fabric: Any,
    obs_space: Any,
    stored_keys: Sequence[str],
    actions_dim: Sequence[int],
    buffer_size: int,
    num_envs: int,
    obs_keys: Sequence[str],
    memmap_dir: Any,
    seed: Optional[int],
    store_next_obs: bool,
) -> Any:
    """Construct the uniform-transition replay for a SAC-family loop: the HBM
    ring (sampled via :meth:`DeviceReplayBuffer.sample_transitions`) when
    :func:`resolve_device_buffer` allows it, else the stock host
    ``ReplayBuffer``. ``stored_keys`` are the observation-space keys the loop
    actually writes (for the footprint estimate); ``obs_keys`` the step-dict
    keys that get a ``next_`` twin under ``sample_next_obs``."""
    from sheeprl_tpu.data.buffers import ReplayBuffer

    est = estimate_transition_bytes(
        obs_space, stored_keys, actions_dim, buffer_size, num_envs, store_next_obs
    )
    if resolve_device_buffer(
        cfg, fabric, obs_space, actions_dim, buffer_size, num_envs, estimated_bytes=est
    ):
        rb = DeviceReplayBuffer(
            buffer_size,
            n_envs=num_envs,
            obs_keys=obs_keys,
            seed=seed,
            **(_mesh_kwargs(fabric)),
        )
        assert ("sharded" in repr(rb)) == (fabric.world_size > 1), repr(rb)
        return rb
    return ReplayBuffer(
        buffer_size,
        num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=memmap_dir,
        seed=seed,
    )


def adapt_restored_buffer(
    rb: Any,
    want_device: bool,
    seed: Optional[int] = None,
    mode: str = "sequence",
    memmap: bool = False,
    memmap_dir: Any = None,
) -> Any:
    """Convert a checkpoint-restored replay buffer to this run's mode —
    checkpoints from either buffer mode resume into either. ``mode`` names
    the host layout: ``sequence`` (Dreamer family,
    ``EnvIndependentReplayBuffer``) or ``transition`` (SAC family, plain
    ``ReplayBuffer``). ``memmap``/``memmap_dir`` apply when a device
    checkpoint materializes as a host buffer — pass the run's
    ``cfg.buffer.memmap`` so a pixel ring does not land in host RAM that a
    fresh run of the same config would have memmapped."""
    from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, ReplayBuffer

    if isinstance(rb, DeviceReplayBuffer):
        if want_device:
            return rb.restore_to_device()
        if mode == "sequence":
            return rb.to_host_buffer(memmap=memmap, memmap_dir=memmap_dir)
        return rb.to_transition_host_buffer(memmap=memmap, memmap_dir=memmap_dir)
    if want_device and isinstance(rb, EnvIndependentReplayBuffer):
        return DeviceReplayBuffer.from_host_buffer(rb, seed=seed)
    if want_device and isinstance(rb, ReplayBuffer):
        return DeviceReplayBuffer.from_transition_host_buffer(rb, seed=seed)
    return rb
