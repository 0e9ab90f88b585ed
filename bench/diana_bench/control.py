"""Schedulers put in the program's place to show that the check fails.

``ControlScheduler`` is the plain reference computed one precision below
what the configurations state (float32 for float64), behind the
``DianaScheduler`` calls the drivers make. ``faulty_scheduler`` wraps the
program's scheduler with one planted fault. Neither is used by a
benchmark run; ``bench/tools/control.py`` and the tests drive them.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from .grids import Grid
from .reference import SchedulerReference

__all__ = ["ControlScheduler", "FAULTS", "SIM_FAULTS", "SimControl",
           "faulty_scheduler", "faulty_sim"]


class ControlScheduler:
    """``place_batch``/``complete`` over ``SchedulerReference(dtype)``."""

    def __init__(self, sites, links, topology=None, dtype=np.float32):
        names = list(sites)
        grid = Grid(
            names=names,
            cap=np.asarray([sites[n].capacity for n in names]),
            queue=np.asarray([sites[n].queue_length for n in names]),
            work=np.asarray([sites[n].waiting_work for n in names]),
            load=np.asarray([sites[n].load for n in names]),
            alive=np.asarray([sites[n].alive for n in names]),
            bw=np.asarray([links[n].bandwidth_Bps for n in names]),
            loss=np.asarray([links[n].loss_rate for n in names]),
            rtt=np.asarray([links[n].rtt_s for n in names]),
            mss=np.asarray([links[n].mss_bytes for n in names]),
        )
        self.ref = SchedulerReference(grid, dtype)
        self.sites = sites

    def _pull(self) -> None:
        # The drivers write queue state back into ``sites`` between
        # groups; read it before placing.
        self.ref.set_state([s.queue_length for s in self.sites.values()],
                           [s.waiting_work for s in self.sites.values()])

    def _push(self) -> None:
        for i, s in enumerate(self.sites.values()):
            s.queue_length = float(self.ref.q[i])
            s.waiting_work = float(self.ref.w[i])

    def place_batch(self, jobs, job_classes=None, **kw):
        self._pull()
        placed = self.ref.place(
            [j.compute_work for j in jobs],
            [j.input_bytes for j in jobs],
            [j.output_bytes for j in jobs],
        )
        names = list(self.sites)
        for job, s in zip(jobs, placed.site):
            job.site = names[s]
        self._push()
        return SimpleNamespace(site_indices=placed.site, costs=placed.cost,
                               sites=[names[s] for s in placed.site])

    def complete(self, job) -> None:
        if job.site is None:
            return
        s = self.sites[job.site]
        s.queue_length = max(0.0, s.queue_length - 1)
        s.waiting_work = max(0.0, s.waiting_work - job.compute_work)


def _state_unchanged(sched):
    """``place_batch`` decides but commits no queue state."""
    inner = sched.place_batch

    def place_batch(jobs, *a, **kw):
        saved = [(s.queue_length, s.waiting_work) for s in sched.sites.values()]
        out = inner(jobs, *a, **kw)
        for s, (q, w) in zip(sched.sites.values(), saved):
            s.queue_length, s.waiting_work = q, w
        return out

    sched.place_batch = place_batch
    return sched


def _half_left_out(sched):
    """``place_batch`` places only the first half of each call's jobs."""
    inner = sched.place_batch

    def place_batch(jobs, *a, **kw):
        return inner(jobs[: (len(jobs) + 1) // 2], *a, **kw)

    sched.place_batch = place_batch
    return sched


def _answer_altered(sched):
    """The first decision of each call moves to the next site."""
    inner = sched.place_batch

    def place_batch(jobs, *a, **kw):
        out = inner(jobs, *a, **kw)
        idx = np.array(out.site_indices, copy=True)
        idx[0] = (idx[0] + 1) % len(sched.sites)
        out.site_indices = idx
        return out

    sched.place_batch = place_batch
    return sched


FAULTS = {
    "state_unchanged": _state_unchanged,
    "half_left_out": _half_left_out,
    "answer_altered": _answer_altered,
}


def faulty_scheduler(fault: str):
    """A ``scheduler_factory`` building the program's scheduler with
    ``fault`` planted."""

    def factory(sites, links, topology):
        from repro.core import DianaScheduler

        return FAULTS[fault](DianaScheduler(sites, links, topology=topology))

    return factory


class SimControl:
    """``GridSim(site_nodes, links=, config=).run(jobs)`` answered by
    ``SimReference(dtype)``."""

    def __init__(self, site_nodes, links, config, dtype=np.float32):
        from .reference_sim import SimReference

        self.names = list(site_nodes)
        S = len(self.names)
        planes = {k: np.empty((S, S)) for k in ("loss", "bw", "rtt", "mss")}
        for i, a in enumerate(self.names):
            for k, b in enumerate(self.names):
                link = links[(a, b)]
                planes["loss"][i, k], planes["bw"][i, k] = link.loss_rate, link.bandwidth_Bps
                planes["rtt"][i, k], planes["mss"][i, k] = link.rtt_s, link.mss_bytes
        self.ref = SimReference(
            list(site_nodes.values()), planes["loss"], planes["bw"], planes["rtt"],
            planes["mss"], migration_interval_s=config.migration_interval_s,
            congestion_window_s=config.congestion_window_s, dtype=dtype)

    def run(self, jobs):
        idx = {n: i for i, n in enumerate(self.names)}
        users = {u: k for k, u in enumerate(sorted({j.user for j in jobs}))}
        out = self.ref.run({
            "user": [users[j.user] for j in jobs],
            "arrival": [j.arrival for j in jobs], "work": [j.work for j in jobs],
            "input_bytes": [j.input_bytes for j in jobs],
            "output_bytes": [j.output_bytes for j in jobs],
            "data_site": [idx[j.data_site] if j.data_site is not None else -1 for j in jobs],
            "origin_site": [idx[j.origin_site] for j in jobs],
        })
        for j, s, st, fi, m in zip(jobs, out["exec_site"], out["start"], out["finish"],
                                   out["migrated"]):
            j.exec_site, j.start, j.finish, j.migrated = self.names[s], st, fi, bool(m)
        n = int(np.sum(out["migrated"]))
        return SimpleNamespace(jobs=jobs, migrations=lambda: n)


def _sim_state_unchanged():
    """The simulator never marks a site's cached cost stale after its
    queue changes, so later decisions read old state."""
    from repro.sim import GridSim

    class Stale(GridSim):
        def _dirty_site(self, name):
            pass

    return Stale


def _sim_half_left_out():
    """Only the first half of each trace is simulated."""
    from repro.sim import GridSim

    class Half(GridSim):
        def run(self, jobs, until=None):
            res = super().run(jobs[: (len(jobs) + 1) // 2], until)
            res.jobs = jobs
            return res

    return Half


def _sim_answer_altered():
    """The first job's execution site is reported as the next site."""
    from repro.sim import GridSim

    class Altered(GridSim):
        def run(self, jobs, until=None):
            res = super().run(jobs, until)
            names = list(self.sites)
            j = res.jobs[0]
            j.exec_site = names[(names.index(j.exec_site) + 1) % len(names)]
            return res

    return Altered


SIM_FAULTS = {
    "state_unchanged": _sim_state_unchanged,
    "half_left_out": _sim_half_left_out,
    "answer_altered": _sim_answer_altered,
}


def faulty_sim(fault: str):
    """A ``scheduler_factory`` for the simulator cells: the program's
    ``GridSim`` with ``fault`` planted."""
    return SIM_FAULTS[fault]()
