"""Simulators put in ``P2PGridSim``'s place to show that the P2P cell's
check fails.

``P2PControl`` is the plain P2P reference computed one precision below
what the configuration states (float32 for float64). ``P2P_FAULTS``
holds the program with one planted fault, and the omniscient
``GridSim``, which decides from live state everywhere and so shows that
the check sees the peers' staleness. None is used by a benchmark run;
``bench/tools/control_p2p.py`` and the tests drive them.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

__all__ = ["P2PControl", "P2P_FAULTS"]


class P2PControl:
    """``P2PGridSim(site_nodes, links=, config=).run(jobs)`` answered by
    ``P2PReference(dtype)``."""

    def __init__(self, site_nodes, links, config, dtype=np.float32):
        from .reference_p2p import P2PReference

        self.names = list(site_nodes)
        idx = {n: i for i, n in enumerate(self.names)}
        S = len(self.names)
        planes = {k: np.empty((S, S)) for k in ("loss", "bw", "rtt", "mss")}
        for i, a in enumerate(self.names):
            for k, b in enumerate(self.names):
                link = links[(a, b)]
                planes["loss"][i, k], planes["bw"][i, k] = link.loss_rate, link.bandwidth_Bps
                planes["rtt"][i, k], planes["mss"][i, k] = link.rtt_s, link.mss_bytes
        self.ref = P2PReference(
            list(site_nodes.values()), planes["loss"], planes["bw"], planes["rtt"],
            planes["mss"], peer_sites=[[idx[n] for n in p] for p in config.peer_sites],
            exchange_interval_s=config.exchange_interval_s,
            exchange_latency_s=config.exchange_latency_s,
            full_sync_every=config.gossip_full_sync_every, wire=config.gossip_wire,
            quant=config.gossip_quant, fanout=config.gossip_fanout,
            transport="lossless" if config.transport_faults is None else "faulty",
            migration_interval_s=config.migration_interval_s,
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
        return SimpleNamespace(jobs=jobs)


def _omniscient():
    """One scheduler with live state everywhere: no peer, no gossip."""
    from repro.sim import GridSim

    return GridSim


def _merge_reversed():
    """Each peer's merge keeps the older of the held and the advertised
    epoch, so gossip never replaces a row with a newer one: planted in
    the per-packet merge and in the exchange's batched one."""
    from repro.core.batch import merge_packed_rows
    from repro.sim import P2PGridSim

    def older_wins(peer, cols, rows, free, alive, versions, stamps, fields):
        return int(merge_packed_rows(
            peer.view, -peer.version, peer.stamp, cols, rows, -np.asarray(versions),
            stamps, alive=alive, protect=peer.home_cols, fields=fields).sum())

    def older_wins_batched(ex, at, v, t):
        V, T, D = ex._V.ravel(), ex._T.ravel(), ex._D.ravel()
        mine = ~ex._HC.ravel()[at]
        held = V[at]
        equal = mine & (v == held)
        put = (mine & (v < held)) | (equal & D[at])
        touch = equal & ~put & (t > T[at])
        T[at[put]] = np.maximum(T[at[put]], t[put])
        T[at[touch]] = t[touch]
        V[at[put]] = v[put]
        D[at[put]] = False
        return put

    class MergeReversed(P2PGridSim):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            for p in self.peers:
                p._merge = older_wins.__get__(p)
            self.exchange._merge_entries = older_wins_batched.__get__(self.exchange)

    return MergeReversed


P2P_FAULTS = {
    "omniscient": _omniscient,
    "merge_reversed": _merge_reversed,
}
