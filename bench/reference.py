"""Plain numpy references for the EJ-FAT data plane, written from
arXiv:2303.16351 and independent of the program under test.

* ``calendar``: the 512-slot calendar of one epoch from its weights
  (largest-remainder quotas, smooth weighted round-robin placement, exact
  quota repair), as the paper's control plane programs it.
* ``route``: parse and validate the LB header, pick the epoch by the
  instance's boundaries, the member by the 9 LSBs of the event number, and
  rewrite to the member's node and receive lane.
* ``plan``: each packet's position among earlier packets to the same node,
  and the per-node totals.
* ``reassembly``: the sorted completion plan of one window.

``control=True`` gives the reference with one stated guarantee broken:
the epoch switch is not hit-less (every event is routed by the newest
calendar, as if the boundary had been dropped). It must come out as not
correct.
"""
from __future__ import annotations

import numpy as np

from traffic import MAGIC, SLOT_MASK, VERSION

SLOTS = 512


def calendar(weights):
    """int32[512] slot -> member index for one epoch's weights."""
    w = np.asarray(weights, np.float64)
    ideal = w / w.sum() * SLOTS
    counts = np.floor(ideal).astype(np.int64)
    counts[(w > 0) & (counts == 0)] = 1
    while counts.sum() > SLOTS:
        counts[int(np.argmax(np.where(counts > 1, counts - ideal, -np.inf)))] -= 1
    order = np.argsort(-(ideal - np.floor(ideal)))
    i = 0
    while counts.sum() < SLOTS:
        if w[order[i % len(order)]] > 0:
            counts[order[i % len(order)]] += 1
        i += 1
    credit = np.zeros(len(w))
    out = np.empty(SLOTS, np.int64)
    for s in range(SLOTS):
        credit += counts
        pick = int(np.argmax(credit))
        out[s] = pick
        credit[pick] -= SLOTS
    # repair: surplus slots (in slot order) go to members short of quota
    have = np.bincount(out, minlength=len(w))
    deficit = [m for m in range(len(w)) if have[m] < counts[m]]
    need = {m: counts[m] - have[m] for m in deficit}
    di = 0
    for s in range(SLOTS):
        m = out[s]
        if have[m] > counts[m] and di < len(deficit):
            d = deficit[di]
            out[s] = d
            have[m] -= 1
            have[d] += 1
            need[d] -= 1
            if need[d] == 0:
                di += 1
    return out


def tables(deployment):
    """Per instance: boundaries and one calendar per epoch."""
    return [dict(inst, calendars=[calendar(w) for w in inst["weights"]])
            for inst in deployment]


def route(headers, iid, tabs, control=False):
    """(member, node, lane, valid) int64[N]; -1 where not valid."""
    h = headers.astype(np.uint64)
    ok = ((h[:, 0] >> np.uint64(16)) == MAGIC) & (
        ((h[:, 0] >> np.uint64(8)) & np.uint64(0xFF)) == VERSION)
    entropy = (h[:, 1] & np.uint64(0xFFFF)).astype(np.int64)
    event = (h[:, 2] << np.uint64(32)) | h[:, 3]
    slot = (event & np.uint64(SLOT_MASK)).astype(np.int64)
    member = np.zeros(len(h), np.int64)
    node = np.zeros(len(h), np.int64)
    lane = np.zeros(len(h), np.int64)
    for k, t in enumerate(tabs):
        rows = iid == k
        epoch = np.zeros(int(rows.sum()), np.int64)
        if control:
            epoch[:] = len(t["calendars"]) - 1
        else:
            for b in t["boundaries"]:
                epoch += event[rows] >= np.uint64(b)
        cal = np.stack(t["calendars"])
        m = cal[epoch, slot[rows]]
        member[rows] = m
        node[rows] = t["node"][m]
        lane[rows] = t["base_lane"][m] + (entropy[rows] & ((1 << t["lane_bits"][m]) - 1))
    neg = lambda x: np.where(ok, x, -1)
    return neg(member), neg(node), neg(lane), ok.astype(np.int64)


def plan(node, n_nodes):
    """(pos, counts): rank among earlier packets to the same node (-1 if
    not valid) and packets per node."""
    pos = np.full(len(node), -1, np.int64)
    ok = np.flatnonzero(node >= 0)
    order = ok[np.argsort(node[ok], kind="stable")]
    sn = node[order]
    first = np.r_[True, sn[1:] != sn[:-1]]
    start = np.maximum.accumulate(np.where(first, np.arange(len(sn)), 0))
    pos[order] = np.arange(len(sn)) - start
    return pos, np.bincount(node[ok], minlength=n_nodes)


def reassembly(event, daq, seg, n_segs, valid):
    """The sorted completion plan of one window: rows sorted by (not
    valid, event, daq, seg, arrival); a group is one (event, daq) among
    valid rows; a duplicate repeats the previous row's segment; a group is
    complete when its unique segments number its first row's ``n_segs``."""
    n = len(event)
    order = np.lexsort((np.arange(n), seg, daq, event, ~valid))
    ok = valid[order]
    ev, dq, sg = event[order], daq[order], seg[order]
    same = np.zeros(n, bool)
    same[1:] = ok[:-1] & (ev[1:] == ev[:-1]) & (dq[1:] == dq[:-1])
    new_group = ok & ~same
    dup = np.zeros(n, bool)
    dup[1:] = ok[1:] & same[1:] & (sg[1:] == sg[:-1])
    unique = ok & ~dup
    gid = np.clip(np.cumsum(new_group) - 1, 0, n - 1)
    counts = np.bincount(gid[unique], minlength=n)
    want = np.zeros(n, np.int64)
    want[gid[new_group]] = n_segs[order][new_group]
    complete = ok & ((counts > 0) & (counts == want))[gid]
    return dict(perm=order, new_group=new_group.astype(np.int64),
                dup=dup.astype(np.int64), unique=unique, complete=complete,
                group_id=gid, n_groups=int(new_group.sum()))

