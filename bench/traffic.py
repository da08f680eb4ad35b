"""The one traffic generator: windows of EJ-FAT packets from a cell's
traffic file, a deployment file and a seed.

It writes the wire format itself (its own copy of the 16-byte LB header and
the 8-byte segmentation header of arXiv:2303.16351 §II-C, §III-A), so the
benchmark's inputs never depend on the program's encoders.

Two kinds of event (``traffic["events"]``):

* ``"bundles"``: each trigger makes one bundle per DAQ under one shared
  64-bit event number; a bundle of ``b`` bytes (normal around
  ``mean_bundle_bytes``, sd ``bundle_sd`` of the mean, at least
  ``min_bundle_bytes``) is cut into ``ceil(b / mtu_payload)`` segments. A
  window is the first ``window_packets`` segments in emission order; the
  next window starts after the event it cut.
* ``"single"``: one packet per event (a bare frame of ``frame_bytes``).

Then, per window: rows are shuffled (WAN reorder) if ``shuffle``; a
``duplicate_fraction`` of rows is overwritten by copies of others; a
``corrupt_fraction`` of headers gets a bad magic. Event numbers start at
``first_event`` and advance by a gap drawn uniformly from ``event_gap``
(``[lo, hi)``) per trigger. The instance of a packet is its DAQ modulo the
instance count (``"instance": "daq"``) or uniform (``"uniform"``).
"""
from __future__ import annotations

import math

import numpy as np

MAGIC = 0x4C42
VERSION = 1
PROTOCOL = 1
SLOT_MASK = 0x1FF


def encode_headers(event, entropy, magic=MAGIC):
    """u32[N, 4] LB header words: magic|version|protocol, rsvd|entropy,
    event high word, event low word."""
    event = np.asarray(event, np.uint64)
    out = np.empty((len(event), 4), np.uint32)
    out[:, 0] = (magic << 16) | (VERSION << 8) | PROTOCOL
    out[:, 1] = np.asarray(entropy, np.uint32) & 0xFFFF
    out[:, 2] = (event >> np.uint64(32)).astype(np.uint32)
    out[:, 3] = (event & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return out


def encode_seg_headers(daq, seg, n_segs, payload_len):
    """u32[N, 2] segmentation header words: daq|seg, n_segs|payload_len."""
    out = np.empty((len(daq), 2), np.uint32)
    out[:, 0] = ((np.asarray(daq, np.uint32) & 0xFFFF) << 16) | (
        np.asarray(seg, np.uint32) & 0xFFFF)
    out[:, 1] = ((np.asarray(n_segs, np.uint32) & 0xFFFF) << 16) | (
        np.asarray(payload_len, np.uint32) & 0xFFFF)
    return out


def _events(rng, traffic, n_trig, first):
    lo, hi = traffic["event_gap"]
    gaps = rng.integers(lo, hi, n_trig - 1)
    return first + np.concatenate([[0], np.cumsum(gaps)]).astype(np.uint64)


def _bundles(rng, traffic, config, n, first):
    """Columns of the first ``n`` segments from ``first`` on, in emission
    order, and the event number the next window starts at."""
    mtu, n_daqs = config["mtu_payload"], config["daqs"]
    mean = traffic["mean_bundle_bytes"]
    segs_per_trigger = n_daqs * max(1, math.ceil(mean / mtu))
    n_trig = 2 * n // segs_per_trigger + 1
    evs = _events(rng, traffic, n_trig, first)
    size = np.maximum(traffic["min_bundle_bytes"],
                      rng.normal(mean, mean * traffic["bundle_sd"],
                                 n_trig * n_daqs)).astype(np.int64)
    nseg = -(-size // mtu)
    start = np.concatenate([[0], np.cumsum(nseg)[:-1]])
    total = int(nseg.sum())
    if total < n:
        raise ValueError("traffic too sparse to fill a window")
    seg = np.arange(total) - np.repeat(start, nseg)
    last = np.repeat(size - (nseg - 1) * mtu, nseg)
    col = dict(
        event=np.repeat(np.repeat(evs, n_daqs), nseg),
        daq=np.repeat(np.tile(np.arange(n_daqs), n_trig), nseg),
        seg=seg, n_segs=np.repeat(nseg, nseg),
        length=np.where(seg == np.repeat(nseg, nseg) - 1, last, mtu),
        entropy=np.repeat(rng.integers(0, 1 << 16, n_trig * n_daqs), nseg))
    col = {k: v[:n] for k, v in col.items()}
    return col, int(col["event"][-1]) + 1


def _singles(rng, traffic, config, n, first):
    evs = _events(rng, traffic, n + 1, first)
    payload = traffic["frame_bytes"] - 14 - 20 - 8 - 16 if "frame_bytes" in traffic \
        else config["mtu_payload"]
    col = dict(event=evs[:n], daq=np.arange(n) % config["daqs"],
               seg=np.zeros(n, np.int64), n_segs=np.ones(n, np.int64),
               length=np.full(n, payload),
               entropy=rng.integers(0, 1 << 16, n))
    return col, int(evs[n])


def make_pool(rng, traffic, config):
    """``traffic["pool_windows"]`` windows of ``window_packets`` rows each.

    Returns stacked host arrays ``headers`` u32[P, N, 4], ``seghdr``
    u32[P, N, 2], ``iid`` i32[P, N] and the plain columns ``event`` u64,
    ``daq``, ``seg``, ``n_segs`` [P, N] that the references read.
    """
    n, pool = traffic["window_packets"], traffic["pool_windows"]
    make = {"bundles": _bundles, "single": _singles}[traffic["events"]]
    ev = int(traffic["first_event"])
    wins = []
    for _ in range(pool):
        col, ev = make(rng, traffic, config, n, ev)
        if traffic["shuffle"]:
            order = rng.permutation(n)
            col = {k: v[order] for k, v in col.items()}
        n_dup = int(traffic["duplicate_fraction"] * n)
        if n_dup:
            dst = rng.choice(n, n_dup, replace=False)
            src = rng.integers(0, n, n_dup)
            for v in col.values():
                v[dst] = v[src]
        magic = np.full(n, MAGIC, np.uint32)
        n_bad = int(traffic["corrupt_fraction"] * n)
        if n_bad:
            magic[rng.choice(n, n_bad, replace=False)] ^= 1
        headers = encode_headers(col["event"], col["entropy"])
        headers[:, 0] = (magic << 16) | (VERSION << 8) | PROTOCOL
        instances = config["instances"]
        if traffic["instance"] == "daq":
            iid = col["daq"] % instances
        else:
            iid = rng.integers(0, instances, n)
        wins.append(dict(
            headers=headers,
            seghdr=encode_seg_headers(col["daq"], col["seg"], col["n_segs"],
                                      col["length"]),
            iid=iid.astype(np.int32), event=col["event"].astype(np.uint64),
            daq=col["daq"].astype(np.int64), seg=col["seg"].astype(np.int64),
            n_segs=col["n_segs"].astype(np.int64)))
    return {k: np.stack([w[k] for w in wins]) for k in wins[0]}


def make_deployment(rng, config, pool_events):
    """Members, weights and epoch boundaries of each instance, drawn from
    the seed: member ``m`` of instance ``k`` is node ``k * members + m``
    with ``2 ** (m % 4)`` receive lanes from lane ``4 m``; each epoch's weights are log-uniform over
    ``weight_range``; instance ``k`` enters epoch ``j`` at the
    ``(j - 1 + (k + 1) / (instances + 1)) / (epochs - 1)`` quantile of the
    pool's event numbers, so every instance switches inside the pool."""
    k_n, m_n = config["instances"], config["members_per_instance"]
    lo, hi = np.log(config["weight_range"][0]), np.log(config["weight_range"][1])
    ev = np.sort(pool_events.reshape(-1))
    out = []
    for k in range(k_n):
        node = k * m_n + np.arange(m_n)
        weights = [np.exp(rng.uniform(lo, hi, m_n)) for _ in range(config["epochs"])]
        span = config["epochs"] - 1
        bounds = [int(ev[int((j + (k + 1) / (k_n + 1)) / span * len(ev))])
                  for j in range(span)]
        out.append(dict(node=node, base_lane=4 * np.arange(m_n),
                        lane_bits=np.arange(m_n) % 4, weights=weights,
                        boundaries=bounds))
    return out
