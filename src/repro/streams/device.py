"""Device-resident fused interval step — the dense state backend.

``KeyedStage(state_backend="device")`` keeps windowed per-key state as
device-resident ``jax.Array``s and advances a whole interval in ONE jitted
step: routing lookup (a dense dest table, cached per ``assignment_version``
— PR 2's cache seam), per-key tuple counts, the window-ring slot fold,
eviction, and the per-task cost bincount all happen on-device; the host only
derives the float64 closed forms (costs, emits, sizes) from the step's
integer outputs. That removes the per-interval lexsort / store-update /
segment-sum host work that dominates the columnar backend's profile.

Layout — dense key-indexed ring
-------------------------------
The columnar host store keeps a *compacted* sorted key column and row-
compacts at every boundary. Sorting is exactly what XLA is worst at relative
to numpy (argsort over 150k int32 measured ~4x slower on CPU), and
scatter/gather against compacted rows would re-sort every interval. The
device backend instead uses the same trick as the ``key_stats`` kernel —
trade the sort for dense compute over a bounded key domain:

* ``vals``  (window+1, domain+1) int32 — the ring of per-interval slots,
* ``pres``  (window+1, domain+1) int32 0/1 — slot-exists flags (slot
  creation is what ``ColumnarSpec.slot_bytes`` charges),

where ``domain`` is a power-of-two high-water mark over ``max key id + 1``
and row ``domain`` is the padding sink: tuple batches are padded to a
power-of-two bucket with key ``domain``, so padded scatters land on a row
that is zeroed/ignored by construction. Window totals are column sums;
eviction multiplies by a (window+1,) keep mask. Nothing is sorted, compiled
shapes never depend on how many keys are live, and both state arrays are
donated back into the next step (donation is gated off on CPU, where XLA
cannot alias buffers across calls).

Per-key counting is mode-split: "max" folding needs a device scatter-max
over the raw tuples, but for "add" operators the only per-tuple quantity is
the histogram — and XLA's CPU scatter-add is serial (measured ~16 ms for a
262k-tuple batch where ``np.bincount`` takes ~1 ms). The add-mode step
therefore takes the host-side ``np.bincount`` histogram as an INPUT (one
(domain+1,) int32 upload, smaller than the padded tuple batch it replaces)
and stays scatter-free; the integer values are identical either way, so
bit-parity is unaffected.

``pres`` is int32 rather than bool deliberately: bool buffers defeat CPU
donation ("donated buffers not usable") and the 0/1 integers multiply
straight into the masking arithmetic.

Bit-identical by construction
-----------------------------
Everything the operators' closed forms need — per-key counts, window and
current-slot totals *before* the update — is integer-valued; the step
returns int32 and the host finishes in float64, so reports match the
object/columnar backends bit-for-bit (``tests/test_engine_device.py``).
The engine's two-macro-batch pause split telescopes for these closed forms
(they are batch-boundary invariant), so the fused step processes the whole
interval as one batch and only the ``buffered`` count is computed host-side.

Ownership is a function of the key
----------------------------------
``dest == F(key)`` and migration moves every key whose dest changed, so a
held key always lives on the task F currently maps it to. The fleet keeps a
host ``task`` mirror (int32, -1 = not held) for ``key_location`` and
migration bookkeeping, but migration itself never touches device state —
state is key-indexed; only ownership labels move, and migrated bytes come
from the ``mem`` mirror's closed-form S(k, w). The
:class:`~repro.streams.state.ColumnarPack` contract is preserved:
:class:`DeviceTaskView` exposes ``extract_batch``/``install_batch`` as
device take/mask slices for ``scale_to``'s reconciliation sweep and for
tests — rebalances never fall back to the object path.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import count, span
from repro.kernels.routing_lookup import _fmix32

from .state import ColumnarPack, ColumnarSpec

_INT32_MIN = np.iinfo(np.int32).min


def to_host(x) -> np.ndarray:
    """Host copy of a device array, as one ``pull`` span counted in
    ``d2h_bytes`` and ``d2h_copies`` (its time includes waiting for the
    step that makes ``x``, or for a copy ``copy_to_host_async`` started).
    A host array passes through untouched."""
    if isinstance(x, np.ndarray):
        return x
    n = int(x.nbytes)
    with span("pull", bytes=n):
        out = np.asarray(x)
    count("d2h_bytes", n)
    count("d2h_copies")
    return out


def to_device(x, sharding=None) -> jax.Array:
    """Upload a host array (onto ``sharding`` when given), counted in
    ``h2d_bytes`` as placed: every device's shard, in the device dtype."""
    out = jnp.asarray(x) if sharding is None else jax.device_put(x, sharding)
    shard = math.prod(out.sharding.shard_shape(out.shape))
    count("h2d_bytes",
          shard * out.dtype.itemsize * len(out.sharding.device_set))
    return out


# XLA cannot alias donated buffers across calls on CPU and warns per call;
# elsewhere donation lets the (window+1, domain+1) state update in place.
_DONATE: Tuple[int, ...] = () if jax.default_backend() == "cpu" else (0, 1)


@functools.partial(jax.jit, donate_argnums=_DONATE)
def _interval_step_add(vals, pres, counts, cur_col, keep_cols):
    """One whole "add"-mode interval against the dense ring — scatter-free.

    Args (device):
      vals/pres: (W1, D+1) int32 state ring (donated).
      counts:    (D+1,) int32 per-key tuple histogram (host ``np.bincount``;
                 the padding row's count is structurally zero).
      cur_col:   (W1,) int32 one-hot of this interval's ring column.
      keep_cols: (W1,) int32 0/1 — columns surviving this boundary's eviction.

    Returns the post-boundary state plus ``obs``, the integer observables
    the host closed forms need stacked into one (4, D+1) int32 array so
    the host pulls them in one copy: rows window/slot totals BEFORE the
    update, then per-key held slot-count and value-sum AFTER eviction. In
    add mode the slot delta IS the count, so the whole update is
    elementwise.
    """
    count("retrace.device.interval_step")
    win0 = vals.sum(axis=0)
    slot0 = (vals * cur_col[:, None]).sum(axis=0)
    seen = (counts > 0).astype(jnp.int32)
    vals = vals + cur_col[:, None] * counts[None, :]
    pres = jnp.maximum(pres, cur_col[:, None] * seen[None, :])
    vals = vals * keep_cols[:, None]
    pres = pres * keep_cols[:, None]
    obs = jnp.stack([win0, slot0, pres.sum(axis=0), vals.sum(axis=0)])
    return vals, pres, obs


@functools.partial(jax.jit, static_argnames=("n_tasks",),
                   donate_argnums=_DONATE)
def _interval_step_max(vals, pres, keys, tvals, dest_dense, cur_col,
                       keep_cols, *, n_tasks: int):
    """One whole "max"-mode interval: scatter-max fold over raw tuples.

    Args (device):
      vals/pres: (W1, D+1) int32 state ring (donated).
      keys:      (Npad,) int32 tuple keys, padded with D.
      tvals:     (Npad,) int32 per-tuple values, padded with INT32_MIN.
      dest_dense:(D+1,) int32 F(k) for every key id (see ``_route_dense``).
      cur_col:   (W1,) int32 one-hot of this interval's ring column.
      keep_cols: (W1,) int32 0/1 — columns surviving this boundary's eviction.

    Returns the post-boundary state, ``obs`` — one (5, D+1) int32 array
    with rows per-key counts, window/slot totals BEFORE the update, held
    slot-count and value-sum AFTER eviction — and the per-task tuple
    bincount. Unlike add mode the fold genuinely needs the raw tuple
    values, so the scatters stay on-device.
    """
    count("retrace.device.interval_step")
    d1 = vals.shape[1]
    pad_row = d1 - 1
    counts = jnp.zeros((d1,), jnp.int32).at[keys].add(jnp.int32(1))
    counts = counts.at[pad_row].set(0)
    win0 = vals.sum(axis=0)
    slot0 = (vals * cur_col[:, None]).sum(axis=0)
    seen = (counts > 0).astype(jnp.int32)
    gmax = jnp.full((d1,), _INT32_MIN, jnp.int32).at[keys].max(tvals)
    newslot = jnp.where(seen > 0, jnp.maximum(slot0, gmax), slot0)
    vals = vals + cur_col[:, None] * (newslot - slot0)[None, :]
    pres = jnp.maximum(pres, cur_col[:, None] * seen[None, :])
    vals = vals * keep_cols[:, None]
    pres = pres * keep_cols[:, None]
    obs = jnp.stack([counts, win0, slot0, pres.sum(axis=0),
                     vals.sum(axis=0)])
    task_counts = jnp.zeros((n_tasks,), jnp.int32).at[dest_dense].add(counts)
    return vals, pres, obs, task_counts


@functools.partial(jax.jit, donate_argnums=_DONATE)
def _evict_step(vals, pres, keep_cols):
    """Boundary eviction for a tuple-free interval (no slot updates); the
    held slot-count and value-sum come stacked, as one (2, D+1) array."""
    count("retrace.device.evict_step")
    vals = vals * keep_cols[:, None]
    pres = pres * keep_cols[:, None]
    return vals, pres, jnp.stack([pres.sum(axis=0), vals.sum(axis=0)])


@functools.partial(jax.jit, donate_argnums=_DONATE)
def _set_cols(vals, pres, cols, new_vals, new_pres):
    """Overwrite whole key columns of the ring (checkpoint, restore and
    ``scale_to`` moves). ``cols`` is padded to a power-of-two bucket with
    the padding-sink column and zero values, so the sink stays zero and a
    handful of compiled shapes serve every batch size."""
    return vals.at[:, cols].set(new_vals), pres.at[:, cols].set(new_pres)


def _pad_cols(cols: np.ndarray, sink: int, vals_cols: np.ndarray,
              pres_cols: np.ndarray):
    """Pad ``cols`` and the (W1, n) column blocks to a power-of-two bucket."""
    n = int(cols.shape[0])
    cap = max(256, 1 << max(0, n - 1).bit_length())
    idx = np.full(cap, sink, dtype=np.int32)
    idx[:n] = cols
    v = np.zeros((vals_cols.shape[0], cap), dtype=np.int32)
    p = np.zeros_like(v)
    v[:, :n] = vals_cols
    p[:, :n] = pres_cols
    return idx, v, p


@functools.partial(jax.jit, static_argnames=("n_dest", "seed"))
def _route_dense(all_keys, tkeys, tdests, *, n_dest: int, seed: int):
    """F(k) for EVERY key id at once: fmix32 hash + table-override scatter.

    The jnp twin of the Pallas ``routing_lookup`` kernel over a dense
    ``arange(domain + 1)`` key column — same mix, same override semantics,
    bit-equal to the host planner's Hash32. Empty table slots (-1) scatter
    onto the padding row, whose dest is never read.
    """
    count("retrace.device.route_dense")
    h = _fmix32(all_keys.astype(jnp.uint32) ^ jnp.uint32(seed & 0xFFFFFFFF))
    base = (h % jnp.uint32(n_dest)).astype(jnp.int32)
    pad_row = all_keys.shape[0] - 1
    ok = (tkeys >= 0) & (tkeys < all_keys.shape[0])
    slot = jnp.where(ok, tkeys, pad_row)
    return base.at[slot].set(jnp.where(ok, tdests, base[pad_row]))


class DeviceStateFleet:
    """Shared device state ring + host mirrors for one stage's task fleet.

    One fleet serves ALL task instances of a stage (state is key-indexed;
    task ownership is the host ``task`` label array), so per-interval work
    is a single fused dispatch regardless of the task count.
    """

    def __init__(self, window: int, spec: ColumnarSpec, min_domain: int = 512):
        if spec.mode not in ("add", "max"):
            raise ValueError(f"unknown columnar mode {spec.mode!r}")
        self.window = window
        self.spec = spec
        self._ncols = window + 1
        self._min_domain = min_domain
        self.domain = 0                    # valid key ids are [0, domain)
        self.col_iv = np.full(self._ncols, -1, dtype=np.int64)
        self.task = np.full(1, -1, dtype=np.int32)       # (domain+1,)
        self.mem = np.zeros(1, dtype=np.float64)         # S(k, w) mirror
        self.vals = jnp.zeros((self._ncols, 1), jnp.int32)
        self.pres = jnp.zeros((self._ncols, 1), jnp.int32)
        self._all_keys = None              # device arange(domain+1) for routing
        self._keys_cap = 0                 # tuple-batch pad bucket (pow2 HWM)
        self._host_vals: Optional[np.ndarray] = None
        self._host_pres: Optional[np.ndarray] = None
        self._host_dirty = True

    # -- shape management -------------------------------------------------------
    def ensure_domain(self, needed: int) -> bool:
        """Grow the dense domain to a power-of-two >= ``needed``.

        Power-of-two high-water sizing keeps compiled shapes stable across
        intervals whose max key id wobbles; growth (a genuinely new shape)
        retraces once and copies live state forward. Returns True on growth.
        """
        if needed <= self.domain:
            return False
        dom = max(self._min_domain, 1 << (int(needed) - 1).bit_length())
        d1 = dom + 1
        vals = jnp.zeros((self._ncols, d1), jnp.int32)
        pres = jnp.zeros((self._ncols, d1), jnp.int32)
        task = np.full(d1, -1, dtype=np.int32)
        mem = np.zeros(d1, dtype=np.float64)
        if self.domain:
            # the old padding row is all-zero by construction; copy real rows
            vals = vals.at[:, :self.domain].set(self.vals[:, :self.domain])
            pres = pres.at[:, :self.domain].set(self.pres[:, :self.domain])
            task[:self.domain] = self.task[:self.domain]
            mem[:self.domain] = self.mem[:self.domain]
        self.domain = dom
        self.vals, self.pres = vals, pres
        self.task, self.mem = task, mem
        self._all_keys = None
        self._host_dirty = True
        return True

    # -- the fused hot path -----------------------------------------------------
    def interval_step(self, keys: np.ndarray, tuple_vals: Optional[np.ndarray],
                      dest_dense, n_tasks: int, keep_cols: np.ndarray,
                      cur_col: np.ndarray, mode: str):
        """Run one interval's fused step: host arrays in, host arrays out.

        Returns ``(counts, win0, slot0, held_cnt, held_sum, task_counts)``,
        the first five host int32 ``(D+1,)`` arrays from ONE device-to-host
        copy of the step's stacked observables (in add mode ``counts`` is
        the host histogram — see the module docstring — and is not pulled).
        ``task_counts`` is None in add mode (derive it from counts + the
        host dest mirror); in max mode it stays on the device, for the
        caller to pull only if its operator needs it.
        """
        with span("step"):
            if mode == "add":
                counts = np.bincount(keys, minlength=self.domain + 1) \
                    .astype(np.int32)
                out = _interval_step_add(self.vals, self.pres,
                                         to_device(counts), to_device(cur_col),
                                         to_device(keep_cols))
                self.vals, self.pres = out[0], out[1]
                self._host_dirty = True
                return (counts, *to_host(out[2]), None)
            n = int(keys.shape[0])
            if n > self._keys_cap:
                self._keys_cap = max(1024, 1 << (n - 1).bit_length())
            cap = self._keys_cap
            kp = np.empty(cap, dtype=np.int32)
            kp[:n] = keys
            kp[n:] = self.domain
            tv = np.empty(cap, dtype=np.int32)
            tv[:n] = tuple_vals
            tv[n:] = _INT32_MIN
            out = _interval_step_max(self.vals, self.pres, to_device(kp),
                                     to_device(tv), dest_dense,
                                     to_device(cur_col), to_device(keep_cols),
                                     n_tasks=n_tasks)
            self.vals, self.pres = out[0], out[1]
            self._host_dirty = True
            return (*to_host(out[2]), out[3])

    def evict(self, keep_cols: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        out = _evict_step(self.vals, self.pres, to_device(keep_cols))
        self.vals, self.pres = out[0], out[1]
        self._host_dirty = True
        held_cnt, held_sum = to_host(out[2])
        return held_cnt, held_sum

    def route_dense(self, tkeys: np.ndarray, tdests: np.ndarray, n_dest: int,
                    seed: int, use_kernel: bool,
                    interpret: Optional[bool]):
        """Dense dest table over arange(domain + 1): kernel or jnp twin."""
        d1 = self.domain + 1
        if self._all_keys is None or int(self._all_keys.shape[0]) != d1:
            self._all_keys = jnp.arange(d1, dtype=jnp.int32)
        tk = to_device(tkeys.astype(np.int32))
        td = to_device(tdests.astype(np.int32))
        if use_kernel:
            from repro.kernels.routing_lookup import routing_lookup
            return routing_lookup(self._all_keys, tk, td, n_dest, seed=seed,
                                  interpret=interpret)
        return _route_dense(self._all_keys, tk, td, n_dest=n_dest, seed=seed)

    def dest_host_dense(self, dev) -> np.ndarray:
        """Host copy of a ``route_dense`` table, aligned to key id.

        Returns ``(domain+1,)`` int64 with ``out[k] == F(k)``. The single-
        device layout already is key-aligned; sharded fleets override this to
        de-interleave their per-shard blocks."""
        return to_host(dev).astype(np.int64)

    # -- host snapshots (pack contract + introspection) -------------------------
    def host_state(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._host_dirty:
            self._host_vals = to_host(self.vals)
            self._host_pres = to_host(self.pres)
            self._host_dirty = False
        return self._host_vals, self._host_pres

    def sizes_matrix(self, rows: np.ndarray) -> np.ndarray:
        """(M, W1) float64 per-column sizes — the ColumnarPack closed form:
        slot creation charges ``slot_bytes``; each folded unit charges
        ``bytes_per_unit`` (identical to the columnar store's accumulation
        because both quantities are integer counts)."""
        host_vals, host_pres = self.host_state()
        pres = host_pres[:, rows].T.astype(np.float64)
        vals = host_vals[:, rows].T.astype(np.float64)
        return self.spec.slot_bytes * pres + self.spec.bytes_per_unit * vals

    def _cols(self, rows: np.ndarray) -> Tuple[np.ndarray, int]:
        """Ring columns of key ids, and the padding-sink column."""
        return rows, self.domain

    def _write_cols(self, rows: np.ndarray, vals_cols: np.ndarray,
                    pres_cols: np.ndarray) -> None:
        cols, sink = self._cols(rows)
        idx, v, p = _pad_cols(cols, sink, vals_cols, pres_cols)
        self.vals, self.pres = _set_cols(self.vals, self.pres, to_device(idx),
                                         to_device(v), to_device(p))
        self._host_dirty = True

    def clear_rows(self, rows: np.ndarray) -> None:
        zeros = np.zeros((self._ncols, rows.shape[0]), np.int32)
        self._write_cols(rows, zeros, zeros)
        self.task[rows] = -1
        self.mem[rows] = 0.0

    def install_rows(self, rows: np.ndarray, vals_cols: np.ndarray,
                     pres_cols: np.ndarray, task_idx: int,
                     sizes_rows: np.ndarray) -> None:
        self._write_cols(rows, vals_cols.T, pres_cols.T)
        self.task[rows] = task_idx
        self.mem[rows] = sizes_rows.sum(axis=1)


class _DeviceKeysView:
    """Dict-like ``store.keys`` surface over one task's ownership labels."""

    def __init__(self, fleet: DeviceStateFleet, index: int):
        self._fleet = fleet
        self._index = index

    def _mask(self) -> np.ndarray:
        return self._fleet.task[:self._fleet.domain] == self._index

    def __len__(self) -> int:
        return int(self._mask().sum())

    def __iter__(self):
        return iter(np.nonzero(self._mask())[0].tolist())

    def __contains__(self, key) -> bool:
        k = int(key)
        return (0 <= k < self._fleet.domain
                and int(self._fleet.task[k]) == self._index)


class DeviceTaskView:
    """One task instance's window onto the shared device fleet.

    Implements the store surface the engine's backend-agnostic code paths
    touch outside the fused step: ``keys`` membership (``key_location``),
    ``sizes_arrays`` (scale_to's reconciliation sweep) and the
    ``extract_batch``/``install_batch`` ColumnarPack contract (migration
    primitives; packs interoperate with the columnar store's layout).
    """

    def __init__(self, fleet: DeviceStateFleet, index: int):
        self.fleet = fleet
        self.index = index

    @property
    def keys(self) -> _DeviceKeysView:
        return _DeviceKeysView(self.fleet, self.index)

    def sizes_arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        fleet = self.fleet
        held = np.nonzero(fleet.task[:fleet.domain] == self.index)[0]
        return held.astype(np.int64), fleet.mem[held]

    def extract_batch(self, keys: np.ndarray) -> ColumnarPack:
        fleet = self.fleet
        arr = np.unique(np.asarray(keys, dtype=np.int64).ravel())
        arr = arr[(arr >= 0) & (arr < fleet.domain)]
        rows = arr[fleet.task[arr] == self.index]
        host_vals, host_pres = fleet.host_state()
        pack = ColumnarPack(rows,
                            host_vals[:, rows].T.astype(np.float64),
                            fleet.sizes_matrix(rows),
                            host_pres[:, rows].T.astype(bool),
                            fleet.col_iv.copy())
        if rows.size:
            fleet.clear_rows(rows)
        return pack

    def install_batch(self, pack: ColumnarPack) -> None:
        fleet = self.fleet
        if not pack.keys.size:
            return
        taken = pack.keys[fleet.task[pack.keys] >= 0]
        if taken.size:
            raise RuntimeError(
                f"key {int(taken[0])} already present on target task")
        live = pack.col_iv >= 0
        conflict = live & (fleet.col_iv >= 0) & (fleet.col_iv != pack.col_iv)
        if conflict.any():
            raise RuntimeError(
                "columnar install across skewed interval clocks: source and "
                "target stores disagree on column contents")
        fleet.col_iv = np.where(live & (fleet.col_iv < 0), pack.col_iv,
                                fleet.col_iv)
        fleet.install_rows(pack.keys, pack.vals, pack.present, self.index,
                           pack.sizes)
