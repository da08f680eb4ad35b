"""Batched CN-side reassembly: sort-based completion detection (paper §II-C).

The per-packet reference (`data/segmentation.Reassembler`) fills a dict
buffer per ``(event_number, daq_id)`` — one Python dict op per segment. The
batched path mirrors PR 1's dispatch algorithm instead: the whole arrival
window is key-sorted on ``(event_hi, event_lo, daq_id, seg_index, arrival)``
with one multi-operand ``lax.sort``; group boundaries and duplicates fall out
of a previous-row comparison on the sorted columns (jnp reference or the
Pallas kernel ``kernels/reassembly.seg_masks``). Each group is then a
contiguous run of sorted rows, so per-group unique-segment counts and the
broadcast of each group's verdict back to its rows are cumulative scans
(``cumsum``, a reverse ``cummin``, a ``cummax``) with no scatter or gather; a
group is complete iff its unique count equals its ``n_segs``. O(N log N)
work, no per-packet host loop.

``BatchReassembler`` carries incomplete groups across windows (loss shows up
as pending buffers), ages them, and times them out after
``timeout_windows`` — every loss/timeout/duplicate is *accounted*, never a
corrupt bundle. The backlog (``n_incomplete``) feeds the control plane via
``telemetry.metrics.TelemetryHub.report_ingest``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.protocol import split64
from repro.data.segmentation import (
    DEFAULT_MTU_PAYLOAD,
    PacketBatch,
    next_pow2 as _next_pow2,
)


def reassembly_plan_np(ev_hi, ev_lo, daq, seg_index, n_segs):
    """Host (numpy) form of ``reassembly_plan`` — same sort-based algorithm,
    no padding (host arrays are dynamically shaped). The CN reassembly daemon
    is a host component in the paper (the LB does not participate in
    reassembly), so this is ``BatchReassembler``'s default engine; the jnp /
    Pallas form exists for device-resident ingest and is property-tested
    equal (tests/test_ingest.py). Returns the same fields in sorted order.
    """
    n = len(ev_hi)
    # np.lexsort is stable: arrival order breaks ties, so the first copy of
    # a duplicated segment stays first (as in the jnp form's arrival key).
    order = np.lexsort((seg_index, daq, ev_lo, ev_hi))
    s_hi, s_lo = ev_hi[order], ev_lo[order]
    s_daq, s_seg = daq[order], seg_index[order]
    same = np.zeros((n,), bool)
    same[1:] = ((s_hi[1:] == s_hi[:-1]) & (s_lo[1:] == s_lo[:-1])
                & (s_daq[1:] == s_daq[:-1]))
    new_group = ~same
    dup = np.zeros((n,), bool)
    dup[1:] = same[1:] & (s_seg[1:] == s_seg[:-1])
    unique = ~dup
    gid = np.cumsum(new_group) - 1
    counts = np.bincount(gid[unique], minlength=int(gid[-1]) + 1 if n else 0)
    gsegs = n_segs[order][new_group]  # each group's first row
    complete = (counts == gsegs)[gid]
    return {
        "perm": order.astype(np.int32), "new_group": new_group, "dup": dup,
        "unique": unique, "complete": complete, "group_id": gid,
        "n_groups": int(new_group.sum()),
    }


@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def reassembly_plan(ev_hi, ev_lo, daq, seg_index, n_segs, valid, *,
                    backend: str = "jnp", interpret: bool | None = None):
    """The device-side reassembly program over one (padded) window.

    All inputs are [N] columns; ``valid`` masks padding rows. Returns a dict
    of [N] arrays *in sorted order* plus the sort permutation:

      perm       int32: original row index of each sorted slot
      new_group  int32: 1 at each group's first sorted row
      dup        int32: 1 on duplicate rows (same (event, daq, seg) as prev)
      unique     bool : valid and not duplicate
      complete   bool : row belongs to a group whose unique count == n_segs
      group_id   int32: dense group index (valid rows; padding rows clamp)
      n_groups   int32 scalar
    """
    n = ev_hi.shape[0]
    # One named scope per step, in program order: the scopes name the
    # compiled instructions (their ``op_name``) and nothing else, so the
    # benchmark's trace reader can give each step its device time.
    with jax.named_scope("reassembly.sort"):
        arrival = jnp.arange(n, dtype=jnp.int32)
        inval = (~valid).astype(jnp.uint32)  # invalid rows sort last
        s_inval, s_hi, s_lo, s_daq, s_seg, s_arr, s_nsegs = jax.lax.sort(
            (inval, ev_hi.astype(jnp.uint32), ev_lo.astype(jnp.uint32),
             daq.astype(jnp.uint32), seg_index.astype(jnp.uint32),
             arrival, n_segs.astype(jnp.int32)),
            num_keys=6,
        )
    with jax.named_scope("reassembly.groups"):
        s_valid = (s_inval == 0).astype(jnp.uint32)
        if backend == "pallas":
            from repro.kernels import reassembly as _k

            new_group, dup = _k.seg_masks(s_valid, s_hi, s_lo, s_daq, s_seg,
                                          interpret=interpret)
        else:
            from repro.kernels import ref as _ref

            new_group, dup = _ref.seg_masks_ref(s_valid, s_hi, s_lo, s_daq, s_seg)
        ok = s_valid > 0
        unique = ok & (dup == 0)
        gid = jnp.cumsum(new_group) - 1  # dense group id along sorted order
        gid_c = jnp.clip(gid, 0, n - 1)
    with jax.named_scope("reassembly.count"):
        # A group is a contiguous run of sorted rows and the running count of
        # unique rows ``cu`` never decreases, so a reverse running minimum of
        # ``cu`` over group-end rows gives each row its group's final ``cu``.
        cu = jnp.cumsum(unique, dtype=jnp.int32)
        next_open = jnp.concatenate(
            [(new_group[1:] > 0) | ~ok[1:], jnp.ones((1,), bool)])
        cu_end = jax.lax.cummin(
            jnp.where(ok & next_open, cu, jnp.iinfo(jnp.int32).max),
            reverse=True)
    with jax.named_scope("reassembly.complete"):
        # A group's first row is unique, so the group's unique count there is
        # ``cu_end - cu + 1``, and its own ``n_segs`` is the expected size.
        start = new_group > 0
        flag = (start & (cu_end - cu + 1 == s_nsegs)).astype(jnp.int32)
        # 2 * row + flag rises with the row, so a running maximum carries the
        # verdict of each row's latest group start forward to it.
        row = jnp.arange(n, dtype=jnp.int32)
        filled = jax.lax.cummax(jnp.where(start, 2 * row + flag, 0))
        complete = ok & ((filled & 1) == 1)
        n_groups = jnp.sum(new_group)
    return {
        "perm": s_arr, "new_group": new_group, "dup": dup, "unique": unique,
        "complete": complete, "group_id": gid_c, "n_groups": n_groups,
    }


@dataclasses.dataclass
class ReassemblyStats:
    n_pushed: int = 0            # segments seen (incl. duplicates)
    n_duplicate: int = 0
    n_completed: int = 0         # bundles assembled
    n_timed_out_groups: int = 0
    n_timed_out_segments: int = 0


class BatchReassembler:
    """Stateful window-at-a-time reassembler over ``PacketBatch`` columns.

    ``push_batch`` merges the window with carried-over incomplete segments,
    runs the plan once, assembles every completed bundle with one gather over
    the payload matrix, and retains the rest with an age bump. A group whose
    newest segment has waited more than ``timeout_windows`` pushes (no
    activity) is dropped whole and accounted once.

    ``backend``: "np" (default — the CN daemon is a host component; numpy
    lexsort form), "jnp" or "pallas" (the device plan, padded to a power of
    two so the jit cache stays small; property-tested equal to "np").
    """

    def __init__(self, mtu_payload: int = DEFAULT_MTU_PAYLOAD,
                 timeout_windows: Optional[int] = None,
                 backend: str = "np", interpret: bool | None = None):
        self.pending = PacketBatch.empty(mtu_payload)
        self.pending_age = np.empty((0,), np.int32)
        self.timeout_windows = timeout_windows
        self.backend = backend
        self.interpret = interpret
        self.stats = ReassemblyStats()
        self.completed: list[tuple[tuple[int, int], np.ndarray]] = []
        # (event, daq) keys expired by the most recent push (empty when none)
        # — callers tracking per-bundle state (simnet's emit-time table) use
        # this to purge entries that will never complete.
        self.last_timed_out_keys: list[tuple[int, int]] = []

    # -- accounting -----------------------------------------------------------
    @property
    def n_incomplete(self) -> int:
        """Distinct (event, daq) groups currently buffered (the backlog)."""
        if len(self.pending) == 0:
            return 0
        keys = np.stack([self.pending.event_number.astype(np.uint64),
                         self.pending.daq_id.astype(np.uint64)], axis=1)
        return int(np.unique(keys, axis=0).shape[0])

    @property
    def n_duplicate(self) -> int:
        return self.stats.n_duplicate

    def drain_completed(self):
        out, self.completed = self.completed, []
        return out

    # -- the batched push -----------------------------------------------------
    def push_batch(self, batch: PacketBatch) -> list[np.ndarray]:
        """Ingest one arrival window; returns payloads completed by it."""
        self.last_timed_out_keys = []
        self.stats.n_pushed += len(batch)
        merged = PacketBatch.concat([self.pending, batch])
        ages = np.concatenate(
            [self.pending_age, np.zeros((len(batch),), np.int32)])
        n = len(merged)
        if n == 0:
            return []
        hi, lo = split64(merged.event_number)
        if self.backend == "np":
            plan = reassembly_plan_np(hi, lo, merged.daq_id,
                                      merged.seg_index, merged.n_segs)
            perm = plan["perm"]
            unique = plan["unique"]
            dup = plan["dup"]
            complete = plan["complete"]
            new_group = plan["new_group"]
        else:
            n_pad = _next_pow2(n)

            def pad(x, dtype):
                out = np.zeros((n_pad,), dtype)
                out[:n] = x
                return jnp.asarray(out)

            valid = np.zeros((n_pad,), bool)
            valid[:n] = True
            plan = reassembly_plan(
                pad(hi, np.uint32), pad(lo, np.uint32),
                pad(merged.daq_id, np.int32), pad(merged.seg_index, np.int32),
                pad(merged.n_segs, np.int32), jnp.asarray(valid),
                backend=self.backend, interpret=self.interpret)
            perm = np.asarray(plan["perm"])
            unique = np.asarray(plan["unique"])
            dup = np.asarray(plan["dup"]) > 0
            complete = np.asarray(plan["complete"])
            new_group = np.asarray(plan["new_group"]) > 0
        group_id = np.asarray(plan["group_id"])
        self.stats.n_duplicate += int(dup.sum())

        done = self._assemble(merged, perm, unique, complete, new_group)

        # Retain incomplete survivors (unique, not complete), age them, and
        # expire groups by *activity*: a group times out only when even its
        # newest segment has waited longer than the window, and then the
        # whole group leaves at once — a group is never split across the
        # timeout boundary or counted twice.
        keep_sorted = unique & ~complete
        rows = perm[keep_sorted]
        self.pending = merged.take(rows)
        self.pending_age = ages[rows] + 1
        if self.timeout_windows is not None and len(self.pending):
            _, gid = np.unique(group_id[keep_sorted], return_inverse=True)
            gmin = np.full((int(gid.max()) + 1,), np.iinfo(np.int32).max)
            np.minimum.at(gmin, gid, self.pending_age)
            expired = gmin[gid] > self.timeout_windows
            if expired.any():
                self.stats.n_timed_out_groups += int(
                    (gmin > self.timeout_windows).sum())
                self.stats.n_timed_out_segments += int(expired.sum())
                rows_exp = np.flatnonzero(expired)
                keys = np.unique(np.stack(
                    [self.pending.event_number[rows_exp].astype(np.uint64),
                     self.pending.daq_id[rows_exp].astype(np.uint64)],
                    axis=1), axis=0)
                self.last_timed_out_keys = [
                    (int(e), int(d)) for e, d in keys.tolist()]
                live = np.flatnonzero(~expired)
                self.pending = self.pending.take(live)
                self.pending_age = self.pending_age[live]
        return done

    def _assemble(self, merged: PacketBatch, perm, unique, complete,
                  new_group) -> list[np.ndarray]:
        """Gather every completed group's bytes in (group, seg) order."""
        sel = unique & complete  # sorted rows of complete groups
        if not sel.any():
            return []
        rows = perm[sel]                       # original rows, in (group, seg) order
        lens = merged.payload_len[rows].astype(np.int64)
        mtu = merged.mtu_payload
        if int(lens.min(initial=mtu)) == mtu:
            if np.array_equal(rows, np.arange(len(rows))):
                flat = merged.payload.reshape(-1)  # in-order window: zero copy
            else:
                flat = merged.payload[rows].reshape(-1)
        else:
            # Piecewise concatenate: full-row runs flatten as-is, the (rare)
            # partial rows are trimmed — no per-byte boolean mask.
            gathered = merged.payload[rows]
            pieces, prev = [], 0
            for p in np.flatnonzero(lens < mtu):
                if p > prev:
                    pieces.append(gathered[prev:p].reshape(-1))
                pieces.append(gathered[p, : lens[p]])
                prev = int(p) + 1
            if prev < len(rows):
                pieces.append(gathered[prev:].reshape(-1))
            flat = np.concatenate(pieces)
        starts = new_group[sel]                # group boundary within selection
        byte_off = np.concatenate([[0], np.cumsum(lens)])
        bounds = byte_off[
            np.concatenate([np.flatnonzero(starts), [len(rows)]])]
        first_rows = rows[starts]
        keys = list(zip(merged.event_number[first_rows].tolist(),
                        merged.daq_id[first_rows].tolist()))
        done = [flat[bounds[g] : bounds[g + 1]] for g in range(len(keys))]
        self.completed.extend(zip(keys, done))
        self.stats.n_completed += len(done)
        return done
