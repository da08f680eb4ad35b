"""Batched ingest path: segmentation parity, WAN batch semantics, sort-based
reassembly (all backends), timeout/loss accounting, telemetry feedback, and
the closed-loop driver (DESIGN.md §Ingest)."""
import functools
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from repro.testing.hypo import given, settings, st

from repro.core import EpochManager, MemberSpec
from repro.core.dataplane import DataPlane
from repro.core.protocol import (
    decode_seg_headers,
    encode_seg_headers,
    split64,
)
from repro.data.daq import DAQConfig, DAQFleet, EventBundle
from repro.data.reassembly import (
    BatchReassembler,
    reassembly_plan,
    reassembly_plan_np,
)
from repro.data.segmentation import (
    PacketBatch,
    batch_from_segments,
    segment_bundle,
    segment_bundles,
)
from repro.data.transport import TransportConfig, WANTransport


def _bundle(nbytes, ev=7, daq=0, entropy=3):
    rng = np.random.default_rng(ev)
    return EventBundle(ev, daq, entropy,
                       rng.integers(0, 256, nbytes).astype(np.uint8))


def _window(n_triggers=10, n_daqs=3, seed=0, mean=25_000):
    fleet = DAQFleet(DAQConfig(n_daqs=n_daqs, mean_bundle_bytes=mean, seed=seed))
    return fleet.bundle_window(n_triggers)


class TestSegHeaders:
    def test_roundtrip_words(self):
        w = encode_seg_headers([3, 70000 & 0xFFFF], [0, 9], [4, 4], [100, 8192 & 0xFFFF])
        f = decode_seg_headers(w)
        assert f["daq_id"].tolist() == [3, 70000 & 0xFFFF]
        assert f["seg_index"].tolist() == [0, 9]
        assert f["n_segs"].tolist() == [4, 4]

    def test_batch_seg_words(self):
        batch = segment_bundles([_bundle(30_000)])
        f = decode_seg_headers(batch.seg_words())
        assert np.array_equal(f["seg_index"], batch.seg_index.astype(np.uint32))
        assert np.array_equal(f["payload_len"],
                              batch.payload_len.astype(np.uint32))


class TestBatchedSegmentation:
    @given(nbytes=st.integers(1, 120_000))
    @settings(max_examples=20)
    def test_parity_with_perpacket(self, nbytes):
        """segment_bundles == stacked segment_bundle, field for field."""
        bundles = [_bundle(nbytes, ev=11, daq=2, entropy=5), _bundle(777)]
        batch = segment_bundles(bundles)
        ref = batch_from_segments(
            [s for b in bundles for s in segment_bundle(b)])
        for f in ("headers", "daq_id", "seg_index", "n_segs", "payload_len",
                  "payload", "event_number", "entropy"):
            assert np.array_equal(getattr(batch, f), getattr(ref, f)), f

    def test_take_and_concat(self):
        batch = segment_bundles([_bundle(20_000), _bundle(9_000, ev=9)])
        idx = np.arange(len(batch))[::-1]
        rev = batch.take(idx)
        assert np.array_equal(rev.seg_index, batch.seg_index[::-1])
        cat = PacketBatch.concat([batch, rev])
        assert len(cat) == 2 * len(batch)

    def test_empty_window(self):
        assert len(segment_bundles([])) == 0


class TestWANBatch:
    @given(seed=st.integers(0, 200))
    @settings(max_examples=15)
    def test_duplicate_follows_original(self, seed):
        """The dup-ordering fix: a duplicate never precedes its first copy,
        in both the batched and the per-packet path."""
        batch = segment_bundles(_window(6, seed=seed))
        cfg = TransportConfig(reorder_window=64, duplicate_prob=0.3,
                              loss_prob=0.05, seed=seed)
        for deliver in ("batch", "list"):
            wan = WANTransport(cfg)
            if deliver == "batch":
                wan.deliver_batch(batch)
            else:
                wan.deliver([s for b in _window(6, seed=seed)
                             for s in segment_bundle(b)])
            src, is_dup = wan.last_delivery
            first = {}
            for pos, (s, d) in enumerate(zip(src, is_dup)):
                if not d:
                    first.setdefault(int(s), pos)
            for pos, (s, d) in enumerate(zip(src, is_dup)):
                if d:
                    assert first[int(s)] < pos

    def test_loss_accounting(self):
        batch = segment_bundles(_window(10))
        wan = WANTransport(TransportConfig(loss_prob=0.2, seed=1))
        out = wan.deliver_batch(batch)
        assert len(out) == len(batch) - wan.n_lost
        assert wan.n_lost > 0

    @given(seed=st.integers(0, 300))
    @settings(max_examples=15)
    def test_deliver_and_deliver_batch_agree(self, seed):
        """Both paths draw from the same per-window stream: identical seeds
        and window sequence => identical delivery order, ``n_lost``/``n_dup``
        counters and ``last_delivery`` bookkeeping. (Historically ``deliver``
        used an independent np.random stream and could silently diverge.)"""
        bundles = _window(5, seed=seed)
        batch = segment_bundles(bundles)
        segs = [s for b in bundles for s in segment_bundle(b)]
        assert len(segs) == len(batch)
        cfg = TransportConfig(reorder_window=32, loss_prob=0.1,
                              duplicate_prob=0.1, seed=seed)
        wan_b, wan_l = WANTransport(cfg), WANTransport(cfg)
        for _ in range(3):  # windows advance in lockstep on both paths
            out_b = wan_b.deliver_batch(batch)
            out_l = wan_l.deliver(segs)
            assert wan_b.n_lost == wan_l.n_lost
            assert wan_b.n_dup == wan_l.n_dup
            np.testing.assert_array_equal(wan_b.last_delivery[0],
                                          wan_l.last_delivery[0])
            np.testing.assert_array_equal(wan_b.last_delivery[1],
                                          wan_l.last_delivery[1])
            np.testing.assert_array_equal(
                out_b.event_number,
                np.asarray([s.event_number for s in out_l], np.uint64))
            np.testing.assert_array_equal(
                out_b.seg_index,
                np.asarray([s.seg_index for s in out_l], np.int32))

    def test_deterministic_per_window(self):
        batch = segment_bundles(_window(5))
        a = WANTransport(TransportConfig(reorder_window=32, seed=4))
        b = WANTransport(TransportConfig(reorder_window=32, seed=4))
        assert np.array_equal(a.deliver_batch(batch).seg_index,
                              b.deliver_batch(batch).seg_index)


class TestBatchReassembler:
    @given(seed=st.integers(0, 500))
    @settings(max_examples=15)
    def test_never_corrupt(self, seed):
        """Property: under loss+dup+reorder, across split windows, every
        completed bundle is byte-identical; losses surface as incomplete or
        timed-out groups — never corrupt output."""
        bundles = _window(8, seed=seed)
        by_key = {(b.event_number, b.daq_id): b.payload for b in bundles}
        wan = WANTransport(TransportConfig(
            reorder_window=64, loss_prob=0.1, duplicate_prob=0.1, seed=seed))
        out = wan.deliver_batch(segment_bundles(bundles))
        ra = BatchReassembler(timeout_windows=8)
        cut = len(out) // 3
        ra.push_batch(out.take(np.arange(cut)))
        ra.push_batch(out.take(np.arange(cut, len(out))))
        for key, payload in ra.completed:
            assert np.array_equal(payload, by_key[key])
        if wan.n_lost == 0:
            assert ra.stats.n_completed == len(by_key)

    def test_backend_parity(self):
        """np / jnp / pallas plans agree on completion, dedup and grouping."""
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        n = 257
        ev = rng.integers(0, 40, n).astype(np.uint64)
        hi, lo = split64(ev)
        daq = rng.integers(0, 4, n).astype(np.int32)
        seg = rng.integers(0, 5, n).astype(np.int32)
        nsg = rng.integers(1, 6, n).astype(np.int32)
        host = reassembly_plan_np(hi, lo, daq, seg, nsg)
        n_pad = 512
        pad = lambda x, d: jnp.asarray(np.concatenate(
            [x, np.zeros((n_pad - n,), d)]).astype(d))
        valid = np.zeros((n_pad,), bool)
        valid[:n] = True
        for backend in ("jnp", "pallas"):
            dev = reassembly_plan(
                pad(hi, np.uint32), pad(lo, np.uint32), pad(daq, np.int32),
                pad(seg, np.int32), pad(nsg, np.int32), jnp.asarray(valid),
                backend=backend, interpret=True)
            assert int(dev["n_groups"]) == host["n_groups"]
            dperm = np.asarray(dev["perm"])[:n]
            assert np.array_equal(dperm, host["perm"])
            assert np.array_equal(np.asarray(dev["group_id"])[:n],
                                  host["group_id"])
            for k in ("new_group", "dup", "unique", "complete"):
                assert np.array_equal(
                    np.asarray(dev[k])[:n].astype(bool),
                    np.asarray(host[k]).astype(bool)), (backend, k)

    def test_duplicates_absorbed(self):
        bundles = [_bundle(30_000)]
        batch = segment_bundles(bundles)
        twice = PacketBatch.concat([batch, batch.take(np.arange(3))])
        ra = BatchReassembler()
        done = ra.push_batch(twice)
        assert len(done) == 1 and np.array_equal(done[0], bundles[0].payload)
        assert ra.n_duplicate == 3

    def test_timeout_accounting(self):
        batch = segment_bundles([_bundle(40_000)])
        ra = BatchReassembler(timeout_windows=2)
        ra.push_batch(batch.take(np.arange(len(batch) - 1)))  # drop last seg
        assert ra.n_incomplete == 1
        empty = batch.take(np.asarray([], np.int64))
        expired_keys = []
        for _ in range(3):
            ra.push_batch(empty)
            expired_keys.extend(ra.last_timed_out_keys)
        assert ra.n_incomplete == 0
        assert ra.stats.n_timed_out_groups == 1
        assert ra.stats.n_timed_out_segments == len(batch) - 1
        assert expired_keys == [(7, 0)]  # the expired (event, daq) surfaced

    def test_timeout_is_group_activity_based(self):
        """A late segment resets its group's timer; when the group finally
        expires it leaves whole and is counted exactly once."""
        rng = np.random.default_rng(0)
        b = EventBundle(42, 0, 1, rng.integers(0, 256, 4 * 2048).astype(np.uint8))
        batch = segment_bundles([b], 2048)
        ra = BatchReassembler(2048, timeout_windows=2)
        empty = batch.take(np.asarray([], np.int64))
        ra.push_batch(batch.take(np.asarray([0, 1])))
        ra.push_batch(empty)
        ra.push_batch(batch.take(np.asarray([2])))  # activity: timer resets
        assert ra.n_incomplete == 1  # segs 0,1 not expired separately
        for _ in range(3):
            ra.push_batch(empty)
        assert ra.stats.n_timed_out_groups == 1
        assert ra.stats.n_timed_out_segments == 3
        assert ra.n_incomplete == 0

    def test_dataplane_facade(self):
        """segment/route/reassemble all through the DataPlane facade."""
        em = EpochManager(max_members=8)
        em.initialize({i: MemberSpec(node_id=i, lane_bits=1) for i in range(4)},
                      {i: 1.0 for i in range(4)})
        dp = DataPlane.from_manager(em, backend="jnp")
        bundles = _window(6)
        batch = dp.segment(bundles)
        import jax.numpy as jnp

        r = dp.route(jnp.asarray(batch.headers))
        member = np.asarray(r.member)
        assert np.asarray(r.valid).all()
        done = 0
        for m in np.unique(member):
            ra = dp.make_reassembler()
            done += len(ra.push_batch(batch.take(np.flatnonzero(member == m))))
        assert done == len(bundles)

    def test_device_plan_reassembler(self):
        em = EpochManager(max_members=8)
        em.initialize({0: MemberSpec(node_id=0)}, {0: 1.0})
        dp = DataPlane.from_manager(em, backend="jnp")
        ra = dp.make_reassembler(device_plan=True)
        assert ra.backend == "jnp"
        bundles = [_bundle(25_000)]
        done = ra.push_batch(segment_bundles(bundles))
        assert len(done) == 1
        assert np.array_equal(done[0], bundles[0].payload)


REASSEMBLY_SCOPES = ("reassembly.sort", "reassembly.groups",
                     "reassembly.count", "reassembly.complete")


def _computations(text):
    """A compiled module's computations, without ``metadata={...}`` or the
    debug tables (file names, source lines) that only metadata refers to."""
    body = "\n".join(line for line in text.splitlines()
                     if line.startswith(("%", "ENTRY", " ", "}")))
    return re.sub(r", metadata=\{[^{}]*\}", "", body)


@functools.lru_cache(maxsize=None)
def _compiled_plans():
    """The compiled text of the jnp ``reassembly_plan`` at a small N, with
    its named scopes and with ``jax.named_scope`` made a no-op."""
    import contextlib
    from unittest import mock

    import jax
    import jax.numpy as jnp

    n = 96
    cols = [jnp.zeros((n,), jnp.uint32)] * 5 + [jnp.ones((n,), bool)]
    scoped = reassembly_plan.lower(*cols, backend="jnp").compile().as_text()
    raw = reassembly_plan.__wrapped__

    # a fresh function (same name and signature), so JAX traces it anew
    @functools.wraps(raw)
    def unscoped(*a, **k):
        return raw(*a, **k)

    with mock.patch.object(jax, "named_scope", lambda name: contextlib.nullcontext()):
        plain = jax.jit(unscoped, static_argnames=("backend", "interpret")).lower(
            *cols, backend="jnp").compile().as_text()
    return scoped, plain


@pytest.mark.parametrize("scope", REASSEMBLY_SCOPES)
def test_reassembly_scope_names_ops_only(scope):
    """Each step of the reassembly program carries its named scope in the
    compiled ops' ``op_name``; without the scopes the compiled program is
    the same, instruction for instruction."""
    scoped, plain = _compiled_plans()
    op_names = re.findall(r'op_name="([^"]*)"', scoped)
    assert any(f"/{scope}/" in name for name in op_names)
    assert not any(f"/{scope}/" in name
                   for name in re.findall(r'op_name="([^"]*)"', plain))
    assert _computations(scoped) == _computations(plain)


def _groups(sizes, n_segs=None, seed=0):
    """Columns of consecutive (event, daq) groups of ``sizes`` segments,
    segment indices 0.. in each, expected size = group size unless given,
    arrival order shuffled."""
    ev = np.repeat(np.arange(len(sizes), dtype=np.uint64) * 7 + 3, sizes)
    daq = np.repeat(np.arange(len(sizes), dtype=np.int32) % 3, sizes)
    seg = np.concatenate([np.arange(k, dtype=np.int32) for k in sizes])
    nsg = np.repeat(np.asarray(sizes if n_segs is None else n_segs,
                               np.int32), sizes)
    order = np.random.default_rng(seed).permutation(len(ev))
    return ev[order], daq[order], seg[order], nsg[order]


def _first_row_decides():
    """Expected sizes differ inside a group; only the first sorted row's
    (lowest segment, earliest arrival) counts."""
    ev = np.asarray([5, 5, 5, 9, 9, 9, 9], np.uint64)
    daq = np.zeros((7,), np.int32)
    seg = np.asarray([2, 0, 1, 1, 2, 0, 0], np.int32)
    nsg = np.asarray([7, 3, 5, 3, 3, 4, 3], np.int32)
    return ev, daq, seg, nsg  # group 5 complete (3 of 3), group 9 not (3 of 4)


def _one_row(n_segs):
    return (np.asarray([11], np.uint64), np.asarray([2], np.int32),
            np.asarray([0], np.int32), np.asarray([n_segs], np.int32))


_empty = np.zeros((0,), np.int32)

# name -> (columns of the valid rows, padded window size)
PLAN_CASES = {
    # groups of 1000 and 100 rows: the second crosses the 1024-row block
    # boundary of ``seg_masks``; the third is one short of its n_segs
    "across_block": (_groups([1000, 100, 40, 60], n_segs=[1000, 100, 41, 60]),
                     2048),
    "one_group_whole_window": (_groups([1024]), 1024),
    "one_group_missing_one": (_groups([1024], n_segs=[1025]), 1024),
    "ends_on_last_row": (_groups([3, 5, 120, 128]), 256),
    "all_padding": ((_empty.astype(np.uint64), _empty, _empty, _empty), 8),
    "all_duplicates": ((np.full((64,), 5, np.uint64), np.ones((64,), np.int32),
                        np.zeros((64,), np.int32), np.ones((64,), np.int32)),
                       64),
    "n_segs_differ_in_group": (_first_row_decides(), 16),
    "n1_complete": (_one_row(1), 1),
    "n1_incomplete": (_one_row(2), 1),
}


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_reassembly_plan_matches_host(case, backend):
    """The device plan equals the host plan on every output, valid rows
    first in the padded window; padding rows are never unique or complete."""
    import jax.numpy as jnp

    (ev, daq, seg, nsg), n_pad = PLAN_CASES[case]
    n = len(ev)
    hi, lo = split64(ev)
    host = reassembly_plan_np(hi, lo, daq, seg, nsg)

    def pad(x, dtype):
        out = np.zeros((n_pad,), dtype)
        out[:n] = x
        return jnp.asarray(out)

    dev = reassembly_plan(
        pad(hi, np.uint32), pad(lo, np.uint32), pad(daq, np.int32),
        pad(seg, np.int32), pad(nsg, np.int32), pad(np.ones(n, bool), bool),
        backend=backend, interpret=True)
    dev = {k: np.asarray(v) for k, v in dev.items()}
    assert int(dev["n_groups"]) == host["n_groups"]
    assert np.array_equal(dev["perm"][:n], host["perm"])
    assert np.array_equal(dev["group_id"][:n], host["group_id"])
    for k in ("new_group", "dup", "unique", "complete"):
        assert np.array_equal(dev[k][:n].astype(bool), host[k]), k
        assert not dev[k][n:].any(), k
    if n:
        # padding rows sort last and keep the last group's id
        assert (dev["group_id"][n:] == host["n_groups"] - 1).all()


def test_reassembly_plan_has_no_scatter_or_gather():
    """At the benchmark's 2^17-row window the compiled jnp plan counts,
    fills and broadcasts by scans alone: no scatter or gather instruction."""
    import jax
    import jax.numpy as jnp

    n = 1 << 17
    cols = ([jax.ShapeDtypeStruct((n,), jnp.uint32)] * 5
            + [jax.ShapeDtypeStruct((n,), jnp.bool_)])
    text = reassembly_plan.lower(*cols, backend="jnp").compile().as_text()
    ops = re.findall(r"\b([a-z][a-z-]*)\(", _computations(text))
    assert "sort" in ops and "reduce-window" in ops
    assert not {"scatter", "gather"} & set(ops)


class TestTelemetryFeedback:
    def test_ingest_backlog_raises_fill(self):
        from repro.telemetry.metrics import TelemetryHub

        hub = TelemetryHub(queue_capacity=8)
        hub.report_step(0, step_time=0.1)
        hub.report_step(1, step_time=0.1)
        hub.report_ingest(0, pending=8, timed_out=2)
        hub.report_ingest(1, pending=0)
        snap = hub.snapshot()
        assert snap[0].fill > snap[1].fill
        assert hub.members[0].ingest_timed_out == 2

    def test_pipeline_surfaces_backlog(self):
        from repro.data.pipeline import StreamingPipeline

        em = EpochManager(max_members=16)
        em.initialize({i: MemberSpec(node_id=i, lane_bits=1) for i in range(4)},
                      {i: 1.0 for i in range(4)})
        p = StreamingPipeline(
            DAQConfig(n_daqs=3, mean_bundle_bytes=20_000, seed=2),
            TransportConfig(reorder_window=16, loss_prob=0.15, seed=2), em)
        p.pump(20)
        stats = p.reassembly_stats()
        backlog = p.ingest_backlog()
        assert stats.n_pushed > 0
        if p.wan.n_lost:
            assert sum(backlog.values()) > 0

    def test_control_plane_feedback_threshold(self):
        from repro.core.control_plane import (LoadBalancerControlPlane,
                                              MemberTelemetry)

        em = EpochManager(max_members=16)
        cp = LoadBalancerControlPlane(em)
        cp.start({i: MemberSpec(node_id=i) for i in range(3)})
        flat = {i: MemberTelemetry(fill=0.5, rate=1.0) for i in range(3)}
        assert cp.feedback(flat, current_event=100) is None  # nothing moved
        skew = {0: MemberTelemetry(fill=0.95), 1: MemberTelemetry(fill=0.1),
                2: MemberTelemetry(fill=0.1)}
        eid = cp.feedback(skew, current_event=200)
        assert eid is not None
        assert cp.weights[0] < cp.weights[1]

    def test_feedback_hysteresis_bounds_epochs(self):
        """Repeated skewed feedback without traffic progress reconfigures at
        most once — the calendar rows can't be exhausted by a hot PI loop."""
        from repro.core.control_plane import (LoadBalancerControlPlane,
                                              MemberTelemetry)

        em = EpochManager(max_members=16)
        cp = LoadBalancerControlPlane(em)
        cp.start({i: MemberSpec(node_id=i) for i in range(3)})
        skew = {0: MemberTelemetry(fill=0.95), 1: MemberTelemetry(fill=0.1),
                2: MemberTelemetry(fill=0.1)}
        ids = [cp.feedback(skew, current_event=100) for _ in range(10)]
        assert sum(x is not None for x in ids) == 1
        assert sum(1 for r in em.records.values() if r.active) <= 2


class TestClosedLoop:
    @pytest.mark.parametrize("scenario", ["loss", "elastic"])
    def test_driver_smoke(self, scenario):
        env = dict(os.environ)
        env["PYTHONPATH"] = ("src" + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else "src")
        env.setdefault("JAX_PLATFORMS", "cpu")
        out = subprocess.run(
            [sys.executable, "scripts/run_closed_loop.py", "--steps", "12",
             "--scenario", scenario],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0, out.stdout + out.stderr
