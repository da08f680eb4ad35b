"""The LB data plane of one chip: ``DataPlane.route`` -> ``.plan`` ->
``.reassembly_plan`` on windows of wire packets, with the stages the
traffic file lists.

Each stage is one jitted program under a name of the benchmark's own
(``bench_route``, ``bench_plan``, ``bench_reassembly``), so the trace
reduction finds it by module name. The instances are programmed through
the program's control plane (``EpochManager``) from a deployment the
benchmark draws from the seed; the reference builds its own tables from the
same deployment.
"""
from __future__ import annotations

import time

import numpy as np

import reference
import traffic as gen
from harness import span


def programs(n_nodes):
    import jax

    from repro.core.protocol import decode_fields, decode_seg_headers

    def bench_route(dp, headers, iid):
        r = dp.route(headers, iid)
        return r.member, r.node, r.lane, r.valid

    def bench_plan(dp, node):
        return dp.plan(node, n_nodes)

    def bench_reassembly(dp, headers, seghdr, valid):
        f, s = decode_fields(headers), decode_seg_headers(seghdr)
        return dp.reassembly_plan(f["event_hi"], f["event_lo"], s["daq_id"],
                                  s["seg_index"], s["n_segs"], valid)

    return {"route": jax.jit(bench_route), "plan": jax.jit(bench_plan),
            "reassembly": jax.jit(bench_reassembly)}


def program_instances(deployment, backend="auto"):
    """Stacked instances programmed through the control plane: each
    instance's first epoch, then a reconfiguration at each boundary."""
    from repro.core import DataPlane, EpochManager, MemberSpec

    managers = []
    for inst in deployment:
        em = EpochManager(max_members=512)
        members = {m: MemberSpec(node_id=int(inst["node"][m]),
                                 base_lane=int(inst["base_lane"][m]),
                                 lane_bits=int(inst["lane_bits"][m]))
                   for m in range(len(inst["node"]))}
        for j, w in enumerate(inst["weights"]):
            weights = {m: float(w[m]) for m in members}
            if j == 0:
                em.initialize(members, weights)
            else:
                em.reconfigure(members, weights, inst["boundaries"][j - 1])
        managers.append(em)
    return DataPlane.from_instances(managers, backend=backend)


class State:
    def __init__(self, config, traffic, seed, devices):
        import jax

        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)
        self.pool = gen.make_pool(rng, traffic, config)
        t1 = time.perf_counter()
        self.deployment = gen.make_deployment(rng, config, self.pool["event"])
        self.dp = program_instances(self.deployment)
        self.n_nodes = config["instances"] * config["members_per_instance"]
        self.stages = traffic["stages"]
        self.prog = programs(self.n_nodes)
        self.device = devices[0]
        self.packets_per_window = traffic["window_packets"]
        self._put = lambda x: jax.device_put(x, self.device)
        self.dp = self._put(self.dp)
        t2 = time.perf_counter()
        self.setup_phases = {"pool": t1 - t0, "tables": t2 - t1}

    def submit(self, i):
        p = i % len(self.pool["headers"])
        with span("bench.h2d"):
            h = self._put(self.pool["headers"][p])
            iid = self._put(self.pool["iid"][p])
            segh = self._put(self.pool["seghdr"][p]) if "reassembly" in self.stages else None
        with span("bench.route"):
            out = list(self.prog["route"](self.dp, h, iid))
        if "plan" in self.stages:
            with span("bench.plan"):
                out += list(self.prog["plan"](self.dp, out[1]))
        if "reassembly" in self.stages:
            with span("bench.reassembly"):
                ra = self.prog["reassembly"](self.dp, h, segh, out[3])
                out += [ra[k] for k in sorted(ra)]
        for x in out:
            x.copy_to_host_async()
        return p, out

    def collect(self, handle):
        p, out = handle
        with span("bench.fetch"):
            out = [np.asarray(x) for x in out]
        split = {"route": out[0:4], "plan": out[4:6], "reassembly": out[-7:]}
        return p, {s: split[s] for s in self.stages}

    def release(self):
        self.dp = None

    def check(self, kept, control=False):
        """Entries that differ from the reference, per stage, summed over
        the kept windows (limit 0: every stage is exact), and the number of
        windows that differed. ``control`` puts the control (the reference
        with its epoch switch broken) in the program's place."""
        tabs = reference.tables(self.deployment)
        bad = {s: 0 for s in self.stages}
        ref, failed = {}, 0
        for _, (p, got) in kept:
            if p not in ref:
                ref[p] = self.reference(p, tabs)
            if control:
                got = self.reference(p, tabs, control=True)
            before = sum(bad.values())
            for s in self.stages:
                bad[s] += sum(int(np.sum(np.asarray(g).astype(np.int64) != w))
                              if np.shape(g) == np.shape(w) else int(np.size(w))
                              for g, w in zip(got[s], ref[p][s]))
            failed += sum(bad.values()) > before
        return {f"{s}_mismatch": (bad[s], 0) for s in self.stages}, failed

    def reference(self, p, tabs, control=False):
        pool = self.pool
        member, node, lane, valid = reference.route(
            pool["headers"][p], pool["iid"][p], tabs, control)
        want = {"route": [member, node, lane, valid]}
        if "plan" in self.stages:
            want["plan"] = list(reference.plan(node, self.n_nodes))
        if "reassembly" in self.stages:
            ra = reference.reassembly(pool["event"][p], pool["daq"][p],
                                      pool["seg"][p], pool["n_segs"][p],
                                      valid > 0)
            want["reassembly"] = [ra[k] for k in sorted(ra)]
        return want


def setup(config, traffic, seed, devices):
    return State(config, traffic, seed, devices)
