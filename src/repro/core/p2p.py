"""Decentralized P2P meta-scheduling (paper §III/§IX).

DIANA is explicitly a *decentralized* Meta Scheduler: every site runs
its own scheduler instance, and the P2P layer exchanges cost/queue
information between peers instead of assuming one omniscient global
view. This module is that layer:

* ``PeerScheduler`` — one site's DIANA instance. It owns its home
  site(s)' **authoritative** state and knows the other S−1 sites only
  through a *world view*: a persistent ``SitePack`` whose remote
  columns were heard from peers, plus per-column ``version`` (the
  owner's monotonic epoch) and ``stamp`` (the owner's clock) vectors.
  Placement runs the pure ``PlacementEngine`` over that view — fresh
  or stale, the algorithm is identical, so a single peer owning every
  site (``single_peer``) is bit-identical to
  ``DianaScheduler.place_batch``.
* ``SiteAdvert`` — the wire unit: one packed (8,) ``SitePack`` column
  (``PACK_FIELDS`` order) plus liveness, free slots, epoch and stamp.
  A full advertisement is one (8, S) float64 array + a version vector,
  ~90 bytes/site.
* ``GossipExchange`` — the epoch-advertisement protocol: each round
  every peer advertises every row it knows (own rows freshly measured,
  remote rows as hearsay) to its fan-out set; receivers keep only
  strictly newer epochs (``merge_packed_rows``), so gossip converges
  and stale hearsay can never roll a row backwards. Fan-out is
  hierarchy-aware over ``GridTopology``: peers inside one RootGrid
  tier exchange directly every round (the SubGrid tier), while across
  RootGrids only each tier's representative talks to the other
  representatives (the RootGrid tier of Fig 5) — message count scales
  with tier sizes, not S².

On a reliable transport with a delay the delta wire is simulated a round
at a time: a round's packets, and later their acks, are one heap entry
each, applied as arrays over the peers' stacked views, with the effects
and in the order of the per-packet path (which the faulty transport,
zero latency and the full wire keep).

Delivery latency models the WAN: adverts sent at t arrive at
t+latency, so a receiver's ``staleness`` of a remote row is
(now − stamp) — the knob Q4 migration uses to decide which peers it
still trusts (``select_peers_batch(..., staleness=, max_staleness=)``).

Two wire formats drive the exchange (the DIANA P2P deployment papers,
arXiv 0707.0862 / 0707.0743, require peer information exchange to
scale with *change rate* and tier size, not S² full-state floods):

* ``wire="full"`` — the original protocol: every round every peer
  re-advertises every full (8,) float64 row it knows (~90 B/site).
* ``wire="delta"`` (default) — the compressed protocol. Epochs open
  only when an owner's measured state actually *changed*, each sender
  keeps a per-receiver last-acked version vector and sends only the
  columns whose epoch advanced since that receiver acknowledged
  (acks ride the same latency-delayed heap), the dynamic owner fields
  (queue/work/load/free_slots) travel quantized to f32 — f16 opt-in —
  while epochs stay exact int64, and site names are interned into a
  per-pair id table sent once (uint16/uint32 column ids afterwards;
  a periodic full sync re-sends the table for new/rejoining peers).
  Unchanged-but-re-measured columns ship as tiny heartbeats (id +
  epoch echo + stamp) so ``staleness`` doesn't decay rows that are
  merely stable, and hearsay a receiver provably hears owner-direct
  in the fan-out schedule is suppressed entirely.
"""
from __future__ import annotations

import heapq
import itertools
import math
import struct
import zlib
from collections import deque
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from . import trace
from .batch import (
    PACK_FIELDS,
    JobPack,
    SitePack,
    merge_packed_rows,
)
from .bulk import BulkGroup, BulkScheduler, GroupPlacement
from .costs import CostWeights, NetworkLink, SiteState
from .engine import PlacementEngine
from .queues import Job
from .scheduler import DianaScheduler, JobClass
from .topology import GridTopology

__all__ = [
    "OWNER_FIELDS",
    "QUANT_FIELDS",
    "SiteAdvert",
    "ExchangeStats",
    "PeerScheduler",
    "GossipExchange",
    "single_peer",
    "advert_wire_bytes",
    "encode_packet",
    "decode_packet",
    "PacketError",
    "ACK_WIRE_BYTES",
]

# The advertised fields a receiver actually merges. The wire row
# carries all of PACK_FIELDS, but path quality (bw/loss/rtt/mss) is a
# *receiver-relative* PingER measurement — the owner's values describe
# its own paths, so applying them would corrupt the receiver's view.
OWNER_FIELDS = ("cap", "queue", "work", "load")

# The *dynamic* owner fields the delta wire quantizes and ships
# (``free_slots`` rides alongside, outside the pack). ``cap`` is
# static after construction (``refresh_dynamic`` never re-reads it and
# every peer bootstraps from the full site dict), so it stays off the
# compressed wire entirely.
QUANT_FIELDS = ("queue", "work", "load")


@dataclass(frozen=True)
class SiteAdvert:
    """One advertised site row: the packed (8,) float64 ``SitePack``
    column in ``PACK_FIELDS`` order plus liveness, free slots, the
    owner's monotonic epoch and the owner's clock at measurement."""

    site: str
    row: np.ndarray            # (8,) float64 — PACK_FIELDS order
    alive: bool
    free_slots: float
    version: int
    stamp: float


def advert_wire_bytes(advert: SiteAdvert) -> int:
    """Serialized size of one advert: 8 f64 row + version + stamp +
    free_slots + alive byte + site name (wire-format compression of
    these rows is a ROADMAP follow-up)."""
    return 8 * 8 + 8 + 8 + 8 + 1 + len(advert.site)


@dataclass
class ExchangeStats:
    """Counters for the exchange cost the p2p bench reports.

    ``bytes_sent`` is accounted from *real serialized sizes*: the delta
    wire counts ``len(payload)`` of each encoded packet plus
    ``ACK_WIRE_BYTES`` per acknowledgement; the full wire counts
    ``advert_wire_bytes`` per advert. ``adverts_sent`` counts advertised
    columns (full rows or delta entries); heartbeats and full syncs are
    broken out separately.
    """

    rounds: int = 0
    adverts_sent: int = 0
    adverts_applied: int = 0
    bytes_sent: int = 0
    deliveries: int = 0
    heartbeats_sent: int = 0
    acks_sent: int = 0
    full_syncs: int = 0
    # -- unreliable-transport counters (zero on a reliable transport) ----
    #: messages the fault model dropped in flight (packets and acks)
    dropped: int = 0
    #: extra copies the fault model injected
    duplicated: int = 0
    #: packets discarded at the receiver for a checksum/decode failure
    corrupted: int = 0
    #: packets discarded by the receiver's replay window (already seen)
    dup_suppressed: int = 0
    #: packets that arrived behind a later-sent packet of the same pair
    reordered: int = 0
    #: ack-timeout retransmissions
    retransmits: int = 0
    #: retransmission budgets exhausted → pair escalated to a forced
    #: table-bearing full sync
    sync_escalations: int = 0

    def as_dict(self) -> dict:
        return {
            "rounds": self.rounds,
            "adverts_sent": self.adverts_sent,
            "adverts_applied": self.adverts_applied,
            "bytes_sent": self.bytes_sent,
            "deliveries": self.deliveries,
            "heartbeats_sent": self.heartbeats_sent,
            "acks_sent": self.acks_sent,
            "full_syncs": self.full_syncs,
            "dropped": self.dropped,
            "duplicated": self.duplicated,
            "corrupted": self.corrupted,
            "dup_suppressed": self.dup_suppressed,
            "reordered": self.reordered,
            "retransmits": self.retransmits,
            "sync_escalations": self.sync_escalations,
        }


# ---------------------------------------------------------------------------
# Delta wire format: encode/decode one sender→receiver packet.
# ---------------------------------------------------------------------------

#: Serialized acknowledgement size: 2 B magic + u16 sender + u64 packet
#: seq + u32 pad — acks carry no column data, only "I have everything
#: packet <seq> advertised", so the sender can advance its per-receiver
#: acked version vector.
ACK_WIRE_BYTES = 16

_WIRE_MAGIC = b"DG"
_WIRE_VERSION = 2
_FLAG_TABLE = 1       # packet carries the interned site-id table
_FLAG_F16 = 2         # quantized payload is float16 (default float32)
_FLAG_WIDE_IDS = 4    # column ids are uint32 (>65535 sites)
_QUANT_DTYPES = {"f32": np.float32, "f16": np.float16}
# version, flags, pair seq, n_table, n_delta, n_hb. The pair seq is the
# sender's per-(sender, receiver) packet counter — the receiver's replay
# window uses it to suppress duplicates and detect reordering on an
# unreliable transport (a retransmitted packet re-ships the identical
# bytes, pair seq included).
_HEADER = struct.Struct("<BBIIII")
_CRC = struct.Struct("<I")


class PacketError(ValueError):
    """A wire buffer could not be decoded as a delta packet: truncated,
    corrupted (checksum mismatch), garbage, or structurally invalid.
    ``decode_packet`` raises this — never a bare ``struct.error`` /
    ``IndexError`` — so receivers on a lossy transport can treat every
    undecodable buffer as one droppable event."""


def encode_packet(
    names: Sequence[str],
    ids: np.ndarray,
    qrows: np.ndarray,
    free: np.ndarray,
    alive: np.ndarray,
    versions: np.ndarray,
    stamps: np.ndarray,
    hb_ids: np.ndarray,
    hb_versions: np.ndarray,
    hb_stamps: np.ndarray,
    *,
    quant: str = "f32",
    include_table: bool = False,
    pair_seq: int = 0,
) -> bytes:
    """Serialize one delta packet.

    ``names`` is the sender's canonical column table (ids are indices
    into it); it travels on the wire only when ``include_table`` (the
    once-per-pair negotiation, re-sent by periodic full syncs so a
    rejoining peer can resynchronize). The delta section carries, per
    advertised column: its interned id, the exact int64 epoch, the f64
    owner stamp, one alive bit, and the ``QUANT_FIELDS`` + free_slots
    payload quantized to ``quant``. The heartbeat section carries
    (id, epoch echo, stamp) triplets for unchanged columns.
    ``pair_seq`` is the per-(sender, receiver) packet counter the
    receiver's replay window keys on; the frame ends in a CRC32 of
    everything before it, so in-flight corruption is detected (and the
    packet dropped) instead of merging garbage into a world view.
    """
    dtype = _QUANT_DTYPES[quant]
    wide = len(names) > 0xFFFF
    id_dt = np.uint32 if wide else np.uint16
    flags = (
        (_FLAG_TABLE if include_table else 0)
        | (_FLAG_F16 if quant == "f16" else 0)
        | (_FLAG_WIDE_IDS if wide else 0)
    )
    n = len(ids)
    qrows = np.asarray(qrows, np.float64)
    if qrows.shape != (len(QUANT_FIELDS), n):
        raise ValueError(
            f"qrows must be ({len(QUANT_FIELDS)}, {n}), got {qrows.shape}"
        )
    parts = [
        _WIRE_MAGIC,
        _HEADER.pack(
            _WIRE_VERSION, flags, pair_seq & 0xFFFFFFFF,
            len(names) if include_table else 0, n, len(hb_ids),
        ),
    ]
    if include_table:
        for name in names:
            b = name.encode("utf-8")
            if len(b) > 255:
                raise ValueError(f"site name too long for wire: {name!r}")
            parts.append(struct.pack("<B", len(b)))
            parts.append(b)
    parts += [
        np.ascontiguousarray(ids, id_dt).tobytes(),
        np.ascontiguousarray(versions, np.int64).tobytes(),
        np.ascontiguousarray(stamps, np.float64).tobytes(),
        np.ascontiguousarray(qrows, dtype).tobytes(),
        np.ascontiguousarray(free, dtype).tobytes(),
        np.packbits(np.asarray(alive, bool)).tobytes(),
        np.ascontiguousarray(hb_ids, id_dt).tobytes(),
        np.ascontiguousarray(hb_versions, np.int64).tobytes(),
        np.ascontiguousarray(hb_stamps, np.float64).tobytes(),
    ]
    body = b"".join(parts)
    return body + _CRC.pack(zlib.crc32(body))


def decode_packet(buf: bytes) -> dict:
    """Inverse of ``encode_packet``. Quantized fields come back as
    float64 (dequantized); epochs come back exactly. Returns a dict
    with ``table`` (list of names, or None when the packet carried no
    table), ``pair_seq``, the delta arrays and the heartbeat arrays.

    Raises :class:`PacketError` on ANY undecodable buffer — truncated,
    bit-flipped (the trailing CRC32 catches it), extended, or plain
    garbage — never a bare ``struct.error``/``IndexError``."""
    if len(buf) < 2 + _HEADER.size + _CRC.size:
        raise PacketError(f"truncated packet ({len(buf)} bytes)")
    if buf[:2] != _WIRE_MAGIC:
        raise PacketError("not a delta-wire packet (bad magic)")
    (crc,) = _CRC.unpack_from(buf, len(buf) - _CRC.size)
    body = buf[: len(buf) - _CRC.size]
    if zlib.crc32(body) != crc:
        raise PacketError("checksum mismatch (corrupted packet)")
    try:
        return _decode_body(body)
    except PacketError:
        raise
    except Exception as exc:  # struct.error, IndexError, UnicodeDecodeError…
        raise PacketError(f"malformed packet: {exc}") from exc


def _decode_body(buf: bytes) -> dict:
    ver, flags, pair_seq, n_table, n, n_hb = _HEADER.unpack_from(buf, 2)
    if ver != _WIRE_VERSION:
        raise PacketError(f"unsupported wire version {ver}")
    off = 2 + _HEADER.size
    table: Optional[list[str]] = None
    if flags & _FLAG_TABLE:
        table = []
        for _ in range(n_table):
            if off >= len(buf):
                raise PacketError("truncated site-id table")
            ln = buf[off]
            off += 1
            if off + ln > len(buf):
                raise PacketError("truncated site-id table entry")
            table.append(buf[off : off + ln].decode("utf-8"))
            off += ln
    id_dt = np.uint32 if flags & _FLAG_WIDE_IDS else np.uint16
    dtype = np.float16 if flags & _FLAG_F16 else np.float32

    def take(dt, count, shape=None):
        nonlocal off
        dt = np.dtype(dt)
        if count < 0 or off + count * dt.itemsize > len(buf):
            raise PacketError("truncated packet section")
        out = np.frombuffer(buf, dt, count=count, offset=off)
        off += count * dt.itemsize
        return out if shape is None else out.reshape(shape)

    ids = take(id_dt, n).astype(np.int64)
    versions = take(np.int64, n).copy()
    stamps = take(np.float64, n).copy()
    qrows = take(dtype, len(QUANT_FIELDS) * n, (len(QUANT_FIELDS), n)).astype(np.float64)
    free = take(dtype, n).astype(np.float64)
    alive = np.unpackbits(take(np.uint8, -(-n // 8) if n else 0), count=n).astype(bool)
    hb_ids = take(id_dt, n_hb).astype(np.int64)
    hb_versions = take(np.int64, n_hb).copy()
    hb_stamps = take(np.float64, n_hb).copy()
    if off != len(buf):
        raise PacketError(f"{len(buf) - off} trailing byte(s) after packet")
    return {
        "table": table,
        "quant": "f16" if flags & _FLAG_F16 else "f32",
        "pair_seq": int(pair_seq),
        "ids": ids,
        "versions": versions,
        "stamps": stamps,
        "rows": qrows,
        "free": free,
        "alive": alive,
        "hb_ids": hb_ids,
        "hb_versions": hb_versions,
        "hb_stamps": hb_stamps,
    }


class PeerScheduler:
    """One home site's DIANA scheduler in the decentralized deployment.

    ``sites``/``links`` bootstrap the world view (the §IX join
    protocol's initial full-state exchange); afterwards only the home
    columns are ever read from authoritative state
    (``refresh_dynamic(only=home)``) — every remote column changes
    exclusively through ``receive``-d adverts. ``home_sites`` is the
    part of the grid this peer owns, ``home`` among them: in the
    simulator, the partition the deployment states (e.g. a RootGrid
    region, homed at its Tier-0 or Tier-1; ``SimConfig.peer_sites``)
    or, without one, a round-robin share. The default is the single
    ``home`` site of the paper's one-scheduler-per-site deployment.
    """

    def __init__(
        self,
        home: str,
        sites: dict[str, SiteState],
        links: dict[str, NetworkLink],
        weights: CostWeights = CostWeights(),
        home_sites: Optional[Sequence[str]] = None,
        order: Optional[Sequence[str]] = None,
        now: float = 0.0,
    ):
        self.home = home
        self.home_names = list(home_sites) if home_sites is not None else [home]
        if home not in self.home_names:
            raise ValueError(f"home {home!r} must be in home_sites {self.home_names!r}")
        self.home_sites = frozenset(self.home_names)
        unknown = self.home_sites - set(sites)
        if unknown:
            raise KeyError(f"home site(s) {sorted(unknown)!r} not in sites")
        self.links = dict(links)
        self.weights = weights
        self.engine = PlacementEngine(weights)
        # Authoritative references for the home partition only; remote
        # SiteState objects are never retained (that's the point).
        self.authoritative: dict[str, SiteState] = {
            n: sites[n] for n in self.home_names
        }
        self.view = SitePack.from_scheduler(sites, links, order=order)
        S = len(self.view.names)
        self._col = {n: i for i, n in enumerate(self.view.names)}
        self.home_cols = np.asarray([n in self.home_sites for n in self.view.names])
        self.version = np.zeros(S, np.int64)
        self.stamp = np.full(S, float(now))
        self.free = np.asarray(
            [sites[n].free_slots for n in self.view.names], np.float64
        )
        # Remote columns this peer has speculatively modified (its own
        # optimistic placement feedback). A dirty row is this peer's
        # *belief*, not the owner's measurement — it must never be
        # re-advertised under the owner's epoch (a receiver would
        # record speculation as owner truth and, because merges need a
        # strictly newer epoch, couldn't be corrected until the owner's
        # next advert). The owner's next applied advert cleans it.
        self._dirty = np.zeros(S, bool)
        # Content of each column at its current epoch (queue, work,
        # load, free, alive): epochs open only when a stamped home
        # re-measurement *differs* from this published snapshot, so the
        # delta wire scales with change rate instead of round rate.
        self._pub = self._published_content()
        # Optional measurement source: when the authority regenerates
        # SiteState snapshots per reading (the grid simulator does),
        # refresh_home pulls fresh ones through this callable.
        self.state_provider: Optional[callable] = None
        # Optional home-column change tracking (enable_home_dirty_tracking):
        # None = disabled (every provider-backed content refresh re-reads
        # the whole home partition, the default); a set = only the named
        # home sites have changed since the last refresh.
        self._home_dirty: Optional[set] = None
        # With tracking on: home sites this peer's own placements wrote
        # (``_commit_home``) since the last stamped refresh, which
        # re-measures them; and whether any home content was re-read or
        # handed over since then (if not, no epoch can open).
        self._home_written: set = set()
        self._home_moved = True

    # -- incremental home refresh ---------------------------------------------
    def enable_home_dirty_tracking(self) -> None:
        """Opt in to narrowed refreshes: after this, a provider-backed
        ``refresh_home`` re-measures only the home sites the authority
        reported dirty via ``mark_home_dirty`` (all of them initially)
        and, when stamped (``now=...``, the exchange round path), those
        this peer's own placements wrote since the last stamped refresh.
        The authority must then report *every* home-state mutation, or
        the view goes stale."""
        self._home_dirty = set(self.home_names)

    def mark_home_dirty(self, name: str) -> None:
        """Note that one home site's authoritative state changed (a
        no-op unless tracking is enabled; foreign names are ignored —
        the caller may own a superset partition map)."""
        if self._home_dirty is not None and name in self.home_sites:
            self._home_dirty.add(name)

    def _published_content(self) -> np.ndarray:
        """The (5, S) advertised-content snapshot the change detector
        compares against: the dynamic owner fields + free + alive."""
        return np.stack([
            self.view.queue, self.view.work, self.view.load,
            self.free, self.view.alive.astype(np.float64),
        ])

    # -- world-view maintenance ------------------------------------------------
    def refresh_home(
        self,
        now: Optional[float] = None,
        states: Optional[dict[str, SiteState]] = None,
    ) -> None:
        """Re-measure the home columns from authoritative state.

        With ``now`` given, every home column gets the fresh stamp and
        the columns whose measured content actually changed open a new
        epoch (the advertisement version) — unchanged columns keep
        their epoch, which is what lets the delta wire skip them. With
        ``now=None`` this is a *content-only* refresh for local
        placement: neither the version nor the stamp moves, so an epoch
        can never open without a stamp (an advert carrying a fresh
        epoch over a frozen stamp would make receivers overstate
        ``staleness()`` and wrongly distrust a fresh peer). ``states``
        swaps in fresh authoritative snapshots first (the simulator
        regenerates ``SiteState`` objects per measurement)."""
        if states is None and self.state_provider is not None:
            if self._home_dirty is not None:
                if not (self._home_dirty or self._home_moved or self._home_written):
                    # Nothing re-read since the last stamp: no epoch opens.
                    if now is not None:
                        self.stamp[self.home_cols] = now
                    return
                # Narrowed refresh: re-measure just the home sites the
                # authority reported dirty (stamped: and those this
                # peer's placements wrote). Unchanged columns would
                # re-read to identical floats, so the narrowing is
                # bit-identical to a full refresh.
                pull = self._home_dirty
                if now is not None and self._home_written:
                    pull = pull | self._home_written
                if pull:
                    names = [n for n in self.home_names if n in pull]
                    for n in names:
                        self.authoritative[n] = self.state_provider(n)
                    self.view.refresh_dynamic(self.authoritative, only=names)
                    for n in names:
                        self.free[self._col[n]] = self.authoritative[n].free_slots
                    self._home_dirty.clear()
                    self._home_moved = True
                if now is not None:
                    self._home_written.clear()
                    self._stamp_home(now)
                return
            states = {n: self.state_provider(n) for n in self.home_names}
        if states is not None:
            for n, st in states.items():
                if n not in self.home_sites:
                    raise KeyError(f"{n!r} is not a home site of peer {self.home!r}")
                self.authoritative[n] = st
        self.view.refresh_dynamic(self.authoritative, only=self.home_names)
        for c in np.flatnonzero(self.home_cols):
            self.free[c] = self.authoritative[self.view.names[c]].free_slots
        self._home_moved = True
        if now is not None:
            self._stamp_home(now)

    def _stamp_home(self, now: float) -> None:
        """Stamp every home column ``now``, opening a new epoch where the
        content differs from the published snapshot (none can when no
        home content was re-read since the last stamp)."""
        self.stamp[self.home_cols] = now
        if not self._home_moved:
            return
        self._home_moved = False
        cols = np.flatnonzero(self.home_cols)
        cur = np.stack([
            self.view.queue[cols], self.view.work[cols], self.view.load[cols],
            self.free[cols], self.view.alive[cols].astype(np.float64),
        ])
        changed = cols[np.any(cur != self._pub[:, cols], axis=0)]
        self.version[changed] += 1
        self._pub[:, cols] = cur

    def staleness(self, now: float) -> np.ndarray:
        """Seconds since each column's row was measured by its owner;
        home columns are always fresh (0)."""
        out = np.maximum(0.0, now - self.stamp)
        out[self.home_cols] = 0.0
        return out

    # -- authoritative-state handover (peer churn) ------------------------------
    def handover(self, names: Optional[Sequence[str]] = None) -> dict:
        """Release (part of) this peer's home partition for another
        peer to ``adopt``.

        The grant carries the authoritative ``SiteState`` references
        plus each column's current epoch, stamp and published-content
        snapshot, so the adopter continues the *same* epoch sequence —
        receivers' strictly-newer merges keep converging across the
        ownership change (a reset epoch would make the adopter's first
        adverts look stale and be dropped grid-wide). ``names=None``
        releases the whole partition; a released column becomes an
        ordinary remote column here (updated only by gossip from the
        new owner). Unknown / non-home names raise ``KeyError``."""
        released = list(self.home_names) if names is None else list(names)
        unknown = set(released) - self.home_sites
        if unknown:
            raise KeyError(
                f"cannot hand over {sorted(unknown)!r}: not home site(s) "
                f"of peer {self.home!r}"
            )
        grant = {
            "names": released,
            "states": {n: self.authoritative[n] for n in released},
            "version": {n: int(self.version[self._col[n]]) for n in released},
            "stamp": {n: float(self.stamp[self._col[n]]) for n in released},
            "pub": {n: self._pub[:, self._col[n]].copy() for n in released},
        }
        gone = set(released)
        for n in released:
            del self.authoritative[n]
        self.home_names = [n for n in self.home_names if n not in gone]
        self.home_sites = frozenset(self.home_names)
        self.home_cols[:] = [n in self.home_sites for n in self.view.names]
        if self._home_dirty is not None:
            self._home_dirty -= gone
        self._home_written -= gone
        self._home_moved = True
        return grant

    def adopt(self, grant: dict) -> None:
        """Take authoritative ownership of a ``handover`` grant.

        The adopted columns join the home partition mid-epoch: version
        and stamp continue from the granted values (monotonic — a
        ``max`` guards against an out-of-order grant) and the published
        -content snapshot transfers, so the next stamped refresh opens
        a new epoch exactly when the content has drifted from what the
        previous owner last advertised. The view re-reads authoritative
        truth immediately (hearsay about sites this peer now *owns*
        must not linger)."""
        names = list(grant["names"])
        unknown = [n for n in names if n not in self._col]
        if unknown:
            raise KeyError(
                f"cannot adopt {unknown!r}: unknown to peer {self.home!r}"
            )
        for n in names:
            c = self._col[n]
            self.authoritative[n] = grant["states"][n]
            self.version[c] = max(int(self.version[c]), grant["version"][n])
            self.stamp[c] = max(float(self.stamp[c]), grant["stamp"][n])
            self._pub[:, c] = grant["pub"][n]
            self._dirty[c] = False
            if n not in self.home_sites:
                self.home_names.append(n)
        self.home_sites = frozenset(self.home_names)
        self.home_cols[:] = [n in self.home_sites for n in self.view.names]
        self.view.refresh_dynamic(self.authoritative, only=names)
        for n in names:
            self.free[self._col[n]] = self.authoritative[n].free_slots
        if self._home_dirty is not None:
            self._home_dirty.update(names)
        self._home_moved = True

    # -- gossip/epoch advertisement --------------------------------------------
    def adverts(self, cols: Optional[Sequence[int]] = None) -> list[SiteAdvert]:
        """Advertise packed rows (gossip: own rows *and* hearsay — the
        per-row version lets receivers keep only what's newer). Rows
        this peer has speculatively modified (optimistic placement
        feedback onto remote sites) are withheld: only owner-measured
        content travels under an owner epoch."""
        idx = np.arange(len(self.view.names)) if cols is None else np.asarray(cols)
        idx = idx[~self._dirty[idx]]
        rows = self.view.pack_rows(idx)
        # Rows are frozen: one adverts() result may be fanned out to (or
        # queued for) several receivers, and no receiver must be able to
        # mutate another's payload through the shared arrays.
        out = []
        for k, c in enumerate(idx):
            row = rows[:, k].copy()
            row.setflags(write=False)
            out.append(
                SiteAdvert(
                    site=self.view.names[c],
                    row=row,
                    alive=bool(self.view.alive[c]),
                    free_slots=float(self.free[c]),
                    version=int(self.version[c]),
                    stamp=float(self.stamp[c]),
                )
            )
        return out

    def receive(self, adverts: Sequence[SiteAdvert]) -> int:
        """Merge advertised rows into the world view, row-versioned:
        only strictly newer epochs apply, and home columns (this peer's
        authority) are never overwritten by hearsay, and only the
        owner-authoritative ``OWNER_FIELDS`` apply — this peer's own
        path measurements (bw/loss/rtt/mss) stay untouched. Receive
        time is deliberately irrelevant: staleness is keyed to the
        *owner's* stamp carried in the advert, so a delayed delivery
        arrives already-aged. Returns the number of applied rows."""
        known = [a for a in adverts if a.site in self._col]
        if not known:
            return 0
        return self._merge(
            cols=np.asarray([self._col[a.site] for a in known], np.int64),
            rows=np.stack([a.row for a in known], axis=1),
            free=np.asarray([a.free_slots for a in known], np.float64),
            alive=np.asarray([a.alive for a in known], bool),
            versions=np.asarray([a.version for a in known], np.int64),
            stamps=np.asarray([a.stamp for a in known], np.float64),
            fields=OWNER_FIELDS,
        )

    def receive_packed(
        self,
        names: Sequence[str],
        qrows: np.ndarray,
        free: np.ndarray,
        alive: np.ndarray,
        versions: np.ndarray,
        stamps: np.ndarray,
    ) -> int:
        """Delta-wire merge: dequantized ``QUANT_FIELDS`` rows
        ((3, k), f64 after dequantization) for the named sites. Same
        row-versioned semantics as ``receive`` — quantization touches
        only the payload floats; epochs are exact, so the
        strictly-newer invariant is unaffected. ``cap`` is not on the
        compressed wire (static; every peer bootstraps it)."""
        keep = [k for k, n in enumerate(names) if n in self._col]
        if not keep:
            return 0
        cols = np.asarray([self._col[names[k]] for k in keep], np.int64)
        rows = np.zeros((len(PACK_FIELDS), len(keep)))
        for r, f in enumerate(QUANT_FIELDS):
            rows[PACK_FIELDS.index(f)] = np.asarray(qrows, np.float64)[r, keep]
        return self._merge(
            cols=cols,
            rows=rows,
            free=np.asarray(free, np.float64)[keep],
            alive=np.asarray(alive, bool)[keep],
            versions=np.asarray(versions, np.int64)[keep],
            stamps=np.asarray(stamps, np.float64)[keep],
            fields=QUANT_FIELDS,
        )

    def refresh_stamps(
        self,
        names: Sequence[str],
        versions: np.ndarray,
        stamps: np.ndarray,
    ) -> int:
        """Heartbeat application: the owner re-measured these columns
        and found them unchanged. A stamp applies only when this peer
        already holds exactly the echoed epoch (same content by the
        one-owner-per-epoch invariant) — a peer that missed an epoch
        ignores the heartbeat and waits for the delta / full sync.
        Returns the number of refreshed stamps."""
        n = 0
        for name, v, s in zip(names, versions, stamps):
            c = self._col.get(name)
            if c is None or self.home_cols[c] or self._dirty[c]:
                continue
            if self.version[c] == v and s > self.stamp[c]:
                self.stamp[c] = float(s)
                n += 1
        return n

    def _merge(self, cols, rows, free, alive, versions, stamps, fields) -> int:
        applied = merge_packed_rows(
            self.view,
            self.version,
            self.stamp,
            cols,
            rows,
            new_version=versions,
            new_stamp=stamps,
            alive=alive,
            protect=self.home_cols,
            fields=fields,
            # Speculatively-modified columns accept an equal-epoch
            # owner advert: canonical content replaces the speculation.
            reclaim=self._dirty,
        )
        if applied.any():
            self.free[cols[applied]] = free[applied]
            self._dirty[cols[applied]] = False  # owner truth replaces speculation
        return int(applied.sum())

    # -- placement over the world view -----------------------------------------
    def rank_sites_batch(
        self,
        jobs: Sequence[Job],
        job_classes: Optional[Sequence[Optional[JobClass]]] = None,
        now: Optional[float] = None,
    ) -> list[list[tuple[str, float]]]:
        self.refresh_home(now)
        return self.engine.rank(self.engine.pack_jobs(jobs, job_classes), self.view)

    def select_sites_batch(
        self,
        jobs: Sequence[Job],
        job_classes: Optional[Sequence[Optional[JobClass]]] = None,
        now: Optional[float] = None,
    ):
        self.refresh_home(now)
        return self.engine.select(self.engine.pack_jobs(jobs, job_classes), self.view)

    def place_batch(
        self,
        jobs: Sequence[Job],
        job_classes: Optional[Sequence[Optional[JobClass]]] = None,
        now: Optional[float] = None,
    ):
        """Batched §V placement against the (possibly stale) world view.

        Remote columns keep the optimistic local feedback (this peer's
        own recent placements — the paper's "after every job we
        calculate the cost to submit the next job", per peer); home
        columns are committed back to the authoritative ``SiteState``.
        With every site home, this is bit-identical to
        ``DianaScheduler.place_batch``.
        """
        self.refresh_home(now)
        placement = self.engine.replay(JobPack.from_jobs(jobs, job_classes), self.view)
        for job, name in zip(jobs, placement.sites):
            job.site = name
        for c in set(int(i) for i in placement.site_indices):
            if not self.home_cols[c]:
                self._dirty[c] = True
        self._commit_home()
        return placement

    def note_remote_placement(self, site: str, work: float) -> None:
        """Optimistic local feedback for a placement committed outside
        this class (the simulator admits jobs at the authoritative
        site): bump the view so this peer's next placement sees it.
        Home columns are skipped — they get truth on the next refresh."""
        c = self._col[site]
        if self.home_cols[c]:
            return
        self.view.queue[c] += 1.0
        self.view.work[c] += work
        self._dirty[c] = True

    def _commit_home(self) -> None:
        for c in np.flatnonzero(self.home_cols):
            st = self.authoritative[self.view.names[c]]
            st.queue_length = float(self.view.queue[c])
            st.waiting_work = float(self.view.work[c])
        self._home_moved = True
        if self._home_dirty is not None:
            self._home_written.update(self.home_names)

    # -- §VIII bulk groups over the world view ---------------------------------
    def view_states(self) -> dict[str, SiteState]:
        """Materialize the world view as a ``SiteState`` dict (for the
        dict-shaped §VIII group logic; per-job placement stays packed)."""
        return {
            n: SiteState(
                name=n,
                capacity=float(self.view.cap[i]),
                queue_length=float(self.view.queue[i]),
                waiting_work=float(self.view.work[i]),
                load=float(self.view.load[i]),
                alive=bool(self.view.alive[i]),
                free_slots=float(self.free[i]),
            )
            for i, n in enumerate(self.view.names)
        }

    def schedule_group(
        self,
        group: BulkGroup,
        max_group_fraction: float = 1.0,
        now: Optional[float] = None,
    ) -> GroupPlacement:
        """§VIII group placement from this peer's world view: the group
        is selected/split exactly like ``BulkScheduler.schedule_group``
        but against advertised (possibly stale) state; commits land in
        the view (and authoritatively for home columns)."""
        self.refresh_home(now)
        states = self.view_states()
        placement = BulkScheduler(
            DianaScheduler(states, self.links, self.weights), max_group_fraction
        ).schedule_group(group)
        # Pull the committed queue/work deltas back into the packed view.
        for i, n in enumerate(self.view.names):
            st = states[n]
            if (
                st.queue_length != self.view.queue[i]
                or st.waiting_work != self.view.work[i]
            ):
                self.view.queue[i] = st.queue_length
                self.view.work[i] = st.waiting_work
                if not self.home_cols[i]:
                    self._dirty[i] = True
        self._commit_home()
        return placement


def single_peer(
    sites: dict[str, SiteState],
    links: dict[str, NetworkLink],
    weights: CostWeights = CostWeights(),
    order: Optional[Sequence[str]] = None,
) -> PeerScheduler:
    """The degenerate 1-peer deployment: every site is home, nothing is
    ever stale — the omniscient single-scheduler special case whose
    placements are bit-identical to ``DianaScheduler``."""
    names = list(sites)
    return PeerScheduler(
        home=names[0], sites=sites, links=links, weights=weights,
        home_sites=names, order=order,
    )


_NONE = np.zeros(0, np.int64)


class _PairStore:
    """The wire state of ``rows`` directed pairs, one row each, as
    arrays (see ``_PairState`` for the fields): a batched round reads
    and writes every pair at once."""

    def __init__(self, rows: int, S: int):
        self.acked = np.full((rows, S), -1, np.int64)
        self.hb_stamp = np.full((rows, S), -np.inf)
        self.send_seq = np.zeros(rows, np.int64)
        self.recv_max = np.full(rows, -1, np.int64)
        self.recv_window = np.zeros(rows, np.uint64)
        self.sync_round = np.full(rows, -1, np.int64)  # -1 = None
        self.table: list = [None] * rows

    def reset(self, k: int) -> None:
        self.acked[k] = -1
        self.hb_stamp[k] = -np.inf
        self.send_seq[k] = 0
        self.recv_max[k] = -1
        self.recv_window[k] = 0
        self.sync_round[k] = -1
        self.table[k] = None


class _StoreInt:
    """A ``_PairState`` integer field: its row of the store's array of
    the same name."""

    def __set_name__(self, owner, name: str) -> None:
        self.name = name

    def __get__(self, st, owner=None) -> int:
        return int(getattr(st._s, self.name)[st._k])

    def __set__(self, st, value: int) -> None:
        getattr(st._s, self.name)[st._k] = value


class _PairState:
    """Per-directed-(sender → receiver) wire state: row ``k`` of a
    ``_PairStore`` (its own one-row store by default).

    ``acked`` and ``hb_stamp`` live at the sender end (what the
    receiver last acknowledged / the stamp last shipped per column);
    ``table`` lives at the receiver end (the sender's interned site-id
    table, set only by decoding a table-bearing packet — ids are
    meaningless until one arrived). ``sync_round`` is the round of the
    last full sync (None forces one: the join/negotiation packet).

    The transport fields support the unreliable wire: ``send_seq`` is
    the sender's per-pair packet counter (stamped into each packet
    header); ``recv_max``/``recv_window`` are the receiver's replay
    state — the highest pair seq seen plus a 64-bit bitmask of the
    seqs just below it, so duplicated deliveries (fault-injected or
    retransmitted after a lost ack) are suppressed exactly once and
    reordering is detected without unbounded memory.
    """

    __slots__ = ("_s", "_k")

    def __init__(self, store: Optional[_PairStore] = None, k: int = 0):
        self._s = _PairStore(1, 0) if store is None else store
        self._k = k

    @property
    def acked(self) -> np.ndarray:          # (S,) int64, -1 = never acked
        return self._s.acked[self._k]

    @property
    def hb_stamp(self) -> np.ndarray:       # (S,) f64 stamp last sent
        return self._s.hb_stamp[self._k]

    @property
    def table(self) -> Optional[list]:
        return self._s.table[self._k]

    @table.setter
    def table(self, value: Optional[list]) -> None:
        self._s.table[self._k] = value

    @property
    def sync_round(self) -> Optional[int]:
        r = int(self._s.sync_round[self._k])
        return None if r < 0 else r

    @sync_round.setter
    def sync_round(self, value: Optional[int]) -> None:
        self._s.sync_round[self._k] = -1 if value is None else value

    send_seq = _StoreInt()
    recv_max = _StoreInt()
    recv_window = _StoreInt()

    def accept_seq(self, s: int) -> tuple[bool, bool]:
        """Advance the replay window with pair seq ``s``. Returns
        ``(fresh, reordered)``: not-fresh means duplicate (or older
        than the 64-seq window — indistinguishable, treated the same);
        reordered means fresh but behind an already-seen packet."""
        recv_max = self.recv_max
        if s > recv_max:
            shift = s - recv_max
            self.recv_window = (
                ((self.recv_window << shift) | (1 << (shift - 1)))
                & 0xFFFFFFFFFFFFFFFF
                if recv_max >= 0 else 0
            )
            self.recv_max = s
            return True, False
        if s == recv_max:
            return False, False  # window bits cover seqs BELOW the max
        behind = recv_max - 1 - s
        if behind >= 64:
            return False, False
        bit = 1 << behind
        window = self.recv_window
        if window & bit:
            return False, False
        self.recv_window = window | bit
        return True, True


@dataclass
class _RoundPlan:
    """A batched round's directed pairs in send order (sender-major)
    with their sender, receiver and pair-store row, and the (pair,
    column) entries a packet that is not a full sync can carry: the
    sender's columns the receiver does not hear owner-direct, with
    their flat indices into the (N, S) peer arrays (``ic``: the
    sender's column, ``at``: the receiver's) and the pair store
    (``kc``). ``unique``: no (receiver, column) is among them twice, so
    no packet of a round sees another's effect; ``home_only``: every
    entry is a column its sender owns. ``pm`` is the (N, N) mask of the
    pairs; ``next_full`` the first round at which one of them is due a
    full sync. ``key`` counts the leaves and joins it was built after."""

    key: int
    pairs: list
    I: np.ndarray
    J: np.ndarray
    K: np.ndarray
    r: np.ndarray
    c: np.ndarray
    ic: np.ndarray
    kc: np.ndarray
    at: np.ndarray
    unique: bool
    home_only: bool
    pm: np.ndarray
    next_full: int = 0


@dataclass
class _RoundBatch:
    """The packets of one batched round in flight: its plan, which
    pairs sent a full sync, their pair seqs and the leaves and joins
    before the send (``rev``). A round where every pair full-syncs
    keeps ``dense``: each packet's column mask and the senders' epochs,
    stamps and dequantized rows (queue, work, load, free slots,
    liveness). Any other round keeps the entries it shipped:
    advertised (``d_*``: pair row, column, epoch, stamp, and the
    dequantized values) and heartbeats (``h_*``: pair row, column,
    epoch echo, stamp). ``steady``: a heartbeat round replayed from the
    plan (``_send_steady``)."""

    plan: _RoundPlan
    full: np.ndarray
    pair_seqs: np.ndarray
    rev: int
    unique: bool = False
    steady: bool = False
    dense: Optional[tuple] = None
    d_r: np.ndarray = field(default_factory=lambda: _NONE)
    d_c: np.ndarray = field(default_factory=lambda: _NONE)
    d_v: np.ndarray = field(default_factory=lambda: _NONE)
    d_t: np.ndarray = field(default_factory=lambda: _NONE)
    d_content: Optional[tuple] = None
    h_r: np.ndarray = field(default_factory=lambda: _NONE)
    h_c: np.ndarray = field(default_factory=lambda: _NONE)
    h_v: np.ndarray = field(default_factory=lambda: _NONE)
    h_t: np.ndarray = field(default_factory=lambda: _NONE)


class _FailureDetector:
    """Phi-accrual-style suspicion on the gaps between packets heard
    from one sender (Hayashibara et al.; the DIANA WAN deployment needs
    peers to *suspect*, not declare, silence — loss bursts and
    partitions look identical at first). Every delivered packet —
    heartbeat-only, duplicate, even one whose payload was then
    discarded — is liveness evidence. ``phi(now)`` is
    −log10 P(gap ≥ now − last) under a normal fit of the recent
    inter-arrival gaps: ~1 per expected interval elapsed silently,
    climbing fast once silence exceeds the observed jitter."""

    __slots__ = ("last", "gaps", "_moments_c", "_suspect_c")

    def __init__(self, window: int = 16):
        self.last: Optional[float] = None
        self.gaps: deque = deque(maxlen=window)
        self._moments_c: Optional[tuple[float, float]] = None
        self._suspect_c: Optional[tuple[float, float]] = None

    def heard(self, now: float) -> None:
        if self.last is not None and now > self.last:
            self.gaps.append(now - self.last)
            self._moments_c = None
            self._suspect_c = None
        self.last = max(self.last, now) if self.last is not None else now

    def _moments(self) -> tuple[float, float]:
        """Normal fit (mean, floored stddev) of the gap window, cached
        until the next arrival — phi is queried far more often than
        packets arrive."""
        if self._moments_c is None:
            m = sum(self.gaps) / len(self.gaps)
            var = sum((g - m) ** 2 for g in self.gaps) / len(self.gaps)
            self._moments_c = (m, max(math.sqrt(var), 0.1 * m, 1e-9))
        return self._moments_c

    @staticmethod
    def _phi_of_gap(gap: float, m: float, s: float) -> float:
        p = 0.5 * math.erfc((gap - m) / (s * math.sqrt(2.0)))
        return -math.log10(max(p, 1e-30))

    def phi(self, now: float) -> float:
        if self.last is None or not self.gaps:
            return 0.0
        gap = now - self.last
        if gap <= 0.0:
            return 0.0
        m, s = self._moments()
        return self._phi_of_gap(gap, m, s)

    def suspect_gap(self, threshold: float) -> float:
        """Smallest silence gap at which ``phi`` reaches ``threshold``
        — phi is monotone in the gap, so suspicion checks reduce to one
        float comparison against this precomputed crossing (bisected on
        the float axis once per arrival history, then cached). +inf
        when unreachable (no gap history yet, or threshold above phi's
        1e-30 probability clamp)."""
        if not self.gaps:
            return math.inf
        c = self._suspect_c
        if c is not None and c[0] == threshold:
            return c[1]
        g = math.inf
        if threshold <= 30.0:            # -log10 clamp: phi never exceeds 30
            m, s = self._moments()
            hi = m + 40.0 * s
            while self._phi_of_gap(hi, m, s) < threshold:
                hi *= 2.0
            lo = 0.0
            while True:
                mid = (lo + hi) * 0.5
                if not lo < mid < hi:
                    break
                if self._phi_of_gap(mid, m, s) >= threshold:
                    hi = mid
                else:
                    lo = mid
            g = hi
        self._suspect_c = (threshold, g)
        return g

    def mean_gap(self) -> Optional[float]:
        if not self.gaps:
            return None
        return self._moments()[0]


class GossipExchange:
    """Drives advertisement rounds between N peers.

    ``topology`` enables the hierarchy-aware fan-out: peers are grouped
    by the RootGrid their home site belongs to; within a group everyone
    exchanges with everyone (SubGrid tier), and each group's
    representative (lowest home name) exchanges with the other groups'
    representatives (RootGrid tier). Without a topology the fan-out is
    a full mesh. ``fanout`` caps a peer's per-round neighbor list,
    rotating deterministically across rounds so coverage stays total.
    ``latency_s`` delays delivery: adverts sent at t arrive at
    t+latency (``deliver_due`` drains what's due; delta-wire acks ride
    the same heap back, so ``in_flight`` counts them too).

    ``wire`` picks the format (module docstring): ``"delta"`` (default)
    sends per-receiver version deltas with quantized payloads
    (``quant``: f32 default, f16 opt-in) plus heartbeats, with a full
    sync + interned-table refresh every ``full_sync_every`` rounds per
    pair; ``"full"`` is the original everything-every-round protocol.

    ``transport`` attaches an unreliable-transport fault model (duck-
    typed; canonically ``repro.sim.faults.TransportFaults``): every
    message — delta packets, full-wire advert datagrams, and the acks
    riding back — then passes through seeded-RNG loss (iid and
    Gilbert–Elliott burst), duplication, reorder jitter, bit
    corruption, and scripted partition windows before (maybe) reaching
    the latency heap. The protocol survives it: per-pair sequence
    numbers + a 64-seq replay window suppress duplicates and flag
    reordering, checksums catch corruption (the packet is dropped, not
    merged), un-acked packets retransmit on an exponential-backoff +
    jitter timer until ``max_retransmits``, after which the pair
    escalates to a forced table-bearing full sync, and a phi-accrual
    failure detector per (receiver, sender) pair turns delivery
    silence into graded suspicion (``suspected_peers``) that the
    simulator feeds into its staleness gating. With no model attached
    (``transport=None``) every new code path is skipped and the
    exchange is bit-identical to the reliable-transport protocol.
    """

    def __init__(
        self,
        peers: Sequence[PeerScheduler],
        topology: Optional[GridTopology] = None,
        latency_s: float = 0.0,
        fanout: Optional[int] = None,
        wire: str = "delta",
        quant: str = "f32",
        full_sync_every: int = 32,
        transport=None,
    ):
        if wire not in ("delta", "full"):
            raise ValueError(f"wire must be 'delta' or 'full', got {wire!r}")
        if quant not in _QUANT_DTYPES:
            raise ValueError(f"quant must be one of {sorted(_QUANT_DTYPES)}")
        if full_sync_every < 1:
            raise ValueError("full_sync_every must be ≥ 1")
        self.peers = list(peers)
        self.transport = transport
        # Seeded per-run transport state (reset_transport re-arms):
        # the RNG every stochastic fault decision draws from, the
        # Gilbert–Elliott bad-state bit per directed pair, and the
        # failure detectors per (receiver, sender) pair.
        self._t_rng = (
            np.random.default_rng(getattr(transport, "seed", 0))
            if transport is not None else None
        )
        self._ge_bad: dict[tuple[int, int], bool] = {}
        self._fd: dict[tuple[int, int], _FailureDetector] = {}
        # Arrival-history revision + cached earliest phi crossing, so
        # the sim's per-event suspicion refresh is O(1) while nothing
        # can have changed (suspicion_quiet_until).
        self._fd_rev = 0
        self._susp_cache: Optional[tuple[int, float]] = None
        # Liveness bits for peer churn (set_active): an inactive peer
        # neither sends nor receives and round() skips its refresh.
        # Must exist before the suppression masks below (they walk
        # neighbors()).
        self._active = [True] * len(self.peers)
        self.topology = topology
        self.latency_s = float(latency_s)
        self.fanout = fanout
        self.wire = wire
        self.quant = quant
        self.full_sync_every = int(full_sync_every)
        self.stats = ExchangeStats()
        self._seq = itertools.count()
        # Heap entries: (due, tiebreak, receiver, kind, payload) with
        # kind "adverts" (full wire: (sender, advert list)), "packet"
        # (delta wire: (sender, packet seq, bytes)), "ack" (delta
        # wire: the acked packet's seq), "rto" (retransmit timer at
        # sender ``receiver``: (target, packet seq, attempt, interval)),
        # or, for a batched round, "batch" (the round's packets: a
        # ``_RoundBatch``) and "batch_ack" (the batch and the indices of
        # its packets being acknowledged).
        self._in_flight: list[tuple[float, int, int, str, object]] = []
        # Delta wire: packets sent but not yet acknowledged, seq →
        # ((sender, receiver), advertised cols, their versions, the
        # encoded bytes — kept so a faulty transport can retransmit).
        self._pending: dict[
            int, tuple[tuple[int, int], np.ndarray, np.ndarray, bytes]
        ] = {}
        self._pairs: dict[tuple[int, int], _PairState] = {}
        # When the peers share one column order, every pair's wire state
        # is row i*N + j (sender i → receiver j) of one store.
        names0 = list(self.peers[0].view.names) if self.peers else []
        shared = all(list(p.view.names) == names0 for p in self.peers)
        N, S = len(self.peers), len(names0)
        self._store = _PairStore(N * N, S) if shared else None
        # The reliable delta wire with a delay is simulated a round at a
        # time (``_round_batched``): a round's packets, and later their
        # acks, are one heap entry each, applied as arrays with the
        # effects, in the order, of the per-packet path. The peers'
        # epoch, stamp, speculation and home vectors then become rows
        # of the exchange's (N, S) arrays (a peer serves one exchange),
        # and so do its view's advertised fields and free slots.
        self._batched = (
            shared and wire == "delta" and transport is None
            and self.latency_s > 0.0
        )
        if self._batched:
            peers = self.peers
            self._V = np.stack([p.version for p in peers])
            self._T = np.stack([p.stamp for p in peers])
            self._D = np.stack([p._dirty for p in peers])
            self._HC = np.stack([p.home_cols for p in peers])
            self._F = np.stack([p.free for p in peers])
            self._Q = np.stack([p.view.queue for p in peers])
            self._W = np.stack([p.view.work for p in peers])
            self._L = np.stack([p.view.load for p in peers])
            self._A = np.stack([p.view.alive for p in peers])
            for k, p in enumerate(peers):
                p.version, p.stamp = self._V[k], self._T[k]
                p._dirty, p.home_cols, p.free = self._D[k], self._HC[k], self._F[k]
                p.view.queue, p.view.work = self._Q[k], self._W[k]
                p.view.load, p.view.alive = self._L[k], self._A[k]
        # The peer of every leave or join (set_active), in order: a
        # batched round records how many came before it.
        self._churn: list[int] = []
        self._plan: Optional[_RoundPlan] = None
        # Steady state of the batched wire, checked before each use:
        # the epochs at the last round that advertised nothing (and
        # their values at the plan's entries), and the epochs,
        # speculation and refreshed entries of the last heartbeat
        # round delivered (see _send_steady, _deliver_batch).
        self._quiet: Optional[tuple] = None
        self._steady: Optional[tuple] = None
        self._table_b: Optional[int] = None  # site-id table bytes
        self._groups = self._tier_groups()
        self._reps = [g[0] for g in self._groups]
        self._group_of = {
            i: gi for gi, g in enumerate(self._groups) for i in g
        }
        self._set_owner_suppression()

    # -- hierarchy-aware fan-out ----------------------------------------------
    def _rootgrid_of(self, home: str) -> str:
        """The RootGrid tier a peer's home site belongs to; an unknown
        site forms its own singleton tier."""
        if self.topology is None:
            return "mesh"
        roots = self.topology.rootgrids
        if home in roots:
            return home
        for site, root in roots.items():
            if home in root.node_table:
                return site
        return home

    def _tier_groups(self) -> list[list[int]]:
        groups: dict[str, list[int]] = {}
        for i, p in enumerate(self.peers):
            groups.setdefault(self._rootgrid_of(p.home), []).append(i)
        return [
            sorted(g, key=lambda i: self.peers[i].home)
            for _, g in sorted(groups.items())
        ]

    def neighbors(self, idx: int, rnd: int) -> list[int]:
        """This round's fan-out set for peer ``idx``. Departed
        (inactive) peers have no neighbors and appear in no one else's
        set; tier representatives are re-derived as the first *active*
        member of each group (identical to the static list while
        everyone is active)."""
        if not self._active[idx]:
            return []
        group = [j for j in self._groups[self._group_of[idx]] if self._active[j]]
        out = [j for j in group if j != idx]
        if idx == group[0]:  # the tier representative bridges tiers
            reps = []
            for g in self._groups:
                for m in g:
                    if self._active[m]:
                        reps.append(m)
                        break
            out += [r for r in reps if r != idx]
        if self.fanout is not None and len(out) > self.fanout:
            start = (rnd * self.fanout) % len(out)
            out = [out[(start + k) % len(out)] for k in range(self.fanout)]
        return out

    def set_active(self, idx: int, active: bool) -> None:
        """Peer churn: flip one peer's liveness. Deactivating (or
        reactivating) a peer resets every directed pair that touches it
        and purges its un-acked packets, so a rejoined peer's first
        contact with each neighbor is a table-bearing full sync
        (``_PairState.sync_round=None``) in *both* directions — the
        rejoiner resynchronizes its world view and its neighbors
        renegotiate theirs of it. The owner-direct suppression masks
        are rebuilt against the surviving fan-out (home partitions may
        have moved via handover/adopt)."""
        if self._active[idx] == bool(active):
            return
        self._active[idx] = bool(active)
        self._churn.append(idx)
        for key in [k for k in self._pairs if idx in k]:
            del self._pairs[key]
        for seq in [s for s, e in self._pending.items() if idx in e[0]]:
            del self._pending[seq]
        self._set_owner_suppression()

    def _set_owner_suppression(self) -> None:
        """(Re)build the owner-direct suppression masks, and their
        (N*N, S) stack for the batched round (zero rows for pairs
        without a mask)."""
        self._owner_suppress = self._owner_suppression_masks()
        if self._store is not None:
            N = len(self.peers)
            self._supp_rows = np.zeros(self._store.acked.shape, bool)
            for (i, j), m in self._owner_suppress.items():
                self._supp_rows[i * N + j] = m

    def _owner_suppression_masks(self) -> dict[tuple[int, int], np.ndarray]:
        """Per directed pair (sender i → receiver j): the sender-column
        mask of hearsay the receiver provably hears owner-direct, so i
        need not forward it. A column qualifies when its owning peer is
        in j's every-round sender set (and isn't i itself — i *is* the
        direct path for its own homes). Only valid when ``fanout`` is
        uncapped: a capped fan-out rotates, so "owner sends to j every
        round" no longer holds and suppression is disabled entirely.
        Receiver-owned columns are always suppressed (protected from
        hearsay anyway)."""
        if self.wire != "delta":
            return {}
        owner_of: dict[str, Optional[int]] = {}
        for i, p in enumerate(self.peers):
            for n in p.home_names:
                owner_of[n] = None if n in owner_of else i  # ambiguous → off
        senders_to: dict[int, set[int]] = {
            j: {
                i
                for i in range(len(self.peers))
                if j in self.neighbors(i, 0)
            }
            for j in range(len(self.peers))
        }
        masks: dict[tuple[int, int], np.ndarray] = {}
        for i, p in enumerate(self.peers):
            for j in range(len(self.peers)):
                if j == i:
                    continue
                direct = (
                    (senders_to[j] if self.fanout is None else set()) | {j}
                )
                masks[(i, j)] = np.asarray(
                    [
                        owner_of.get(n) is not None
                        and owner_of[n] != i
                        and owner_of[n] in direct
                        for n in p.view.names
                    ]
                )
        return masks

    def _pair(self, i: int, j: int) -> _PairState:
        st = self._pairs.get((i, j))
        if st is None:
            if self._store is not None:
                k = i * len(self.peers) + j
                self._store.reset(k)
                st = _PairState(self._store, k)
            else:
                st = _PairState(_PairStore(1, len(self.peers[i].view.names)))
            self._pairs[(i, j)] = st
        return st

    # -- unreliable transport --------------------------------------------------
    def reset_transport(self) -> None:
        """Re-arm the transport fault model for a fresh run: re-seed
        the RNG (so reruns replay the same loss/duplication/corruption
        draws), clear the Gilbert–Elliott chain state and failure
        detectors, and drop in-flight messages plus pending
        retransmissions. No-op without a model attached, so the
        reliable-transport exchange is untouched."""
        if self.transport is None:
            return
        self._t_rng = np.random.default_rng(getattr(self.transport, "seed", 0))
        self._ge_bad.clear()
        self._fd.clear()
        self._fd_rev += 1
        self._susp_cache = None
        self._in_flight.clear()
        self._pending.clear()

    def _rto_initial(self) -> float:
        """First ack-timeout: configured ``rto_s`` if set, else four
        one-way latencies (two RTTs of headroom) floored at 1 s."""
        rto = getattr(self.transport, "rto_s", None)
        if rto is not None and rto > 0.0:
            return float(rto)
        return max(4.0 * self.latency_s, 1.0)

    def _transport_drops(self, i: int, j: int, now: float) -> bool:
        """One loss decision for a message i→j: scripted partition
        windows first (deterministic), then the Gilbert–Elliott burst
        chain (one state step per message on the directed pair), then
        iid loss. Zero-rate layers draw nothing from the RNG."""
        t = self.transport
        if t.partitioned(self.peers[i].home, self.peers[j].home, now):
            return True
        if t.burst_p > 0.0:
            bad = self._ge_bad.get((i, j), False)
            if bad:
                if float(self._t_rng.random()) < t.burst_r:
                    bad = False
            elif float(self._t_rng.random()) < t.burst_p:
                bad = True
            self._ge_bad[(i, j)] = bad
            if bad and float(self._t_rng.random()) < t.burst_loss:
                return True
        return t.loss > 0.0 and float(self._t_rng.random()) < t.loss

    def _reorder_delay(self) -> float:
        t = self.transport
        if t.reorder_jitter_s <= 0.0:
            return 0.0
        return float(self._t_rng.random()) * t.reorder_jitter_s

    def _maybe_corrupt(self, buf: bytes) -> bytes:
        """Flip one random bit with probability ``transport.corrupt``;
        the receiver's checksum catches it and drops the packet."""
        t = self.transport
        if t.corrupt <= 0.0 or float(self._t_rng.random()) >= t.corrupt:
            return buf
        mutated = bytearray(buf)
        k = int(self._t_rng.integers(len(mutated)))
        mutated[k] ^= 1 << int(self._t_rng.integers(8))
        return bytes(mutated)

    def _send_message(
        self,
        now: float,
        i: int,
        j: int,
        kind: str,
        payload,
        seq_key: Optional[int] = None,
        tiebreak: Optional[int] = None,
    ) -> None:
        """Route one message through the (possibly faulty) transport.
        With no model attached this is exactly the reliable path: one
        copy, fixed latency, applied inline at zero latency (so
        adverts still cascade through the mesh within a round). With a
        model, the message first survives partition/burst/iid loss;
        each surviving copy (a duplicate may ride along) then picks up
        reorder jitter and — for encoded packets — possible bit
        corruption before entering the latency heap."""
        t = self.transport
        delays: list[float] = []
        if t is None:
            delays.append(0.0)
        else:
            if self._transport_drops(i, j, now):
                self.stats.dropped += 1
            else:
                delays.append(self._reorder_delay())
                if t.duplicate > 0.0 and float(self._t_rng.random()) < t.duplicate:
                    self.stats.duplicated += 1
                    delays.append(self._reorder_delay())
        lat = max(self.latency_s, 0.0)
        for copy_idx, extra in enumerate(delays):
            pl = payload
            if t is not None and kind == "packet":
                pl = self._maybe_corrupt(pl)
            elif t is not None and kind == "adverts" and t.corrupt > 0.0:
                # Object payload (no bytes to flip): a corrupted
                # full-wire datagram fails its checksum on arrival and
                # is discarded whole; the next round re-floods it.
                if float(self._t_rng.random()) < t.corrupt:
                    self.stats.corrupted += 1
                    continue
            due = now + lat + extra
            if due <= now:
                if kind == "packet":
                    self._deliver_packet(now, i, j, pl, seq_key)
                elif kind == "adverts":
                    self._heard(j, i, now)
                    self.stats.adverts_applied += self.peers[j].receive(pl)
                    self.stats.deliveries += 1
                else:  # "ack"
                    self._apply_ack(pl)
                continue
            tb = (
                tiebreak
                if tiebreak is not None and copy_idx == 0
                else next(self._seq)
            )
            if kind == "packet":
                hp: object = (i, seq_key, pl)
            elif kind == "adverts":
                hp = (i, pl)
            else:
                hp = pl
            heapq.heappush(self._in_flight, (due, tb, j, kind, hp))

    def _schedule_rto(
        self, now: float, i: int, j: int, seq: int, attempt: int, interval: float
    ) -> None:
        """Arm (or re-arm, backed off) the ack-timeout for packet
        ``seq``; the fire time is jittered so synchronized rounds don't
        retransmit in lockstep."""
        jitter = 1.0 + getattr(self.transport, "rto_jitter", 0.0) * float(
            self._t_rng.random()
        )
        heapq.heappush(
            self._in_flight,
            (
                now + interval * jitter,
                next(self._seq),
                i,
                "rto",
                (j, seq, attempt, interval),
            ),
        )

    def _fire_rto(self, now: float, i: int, payload) -> None:
        """An ack-timeout fired at sender ``i``: if the packet is still
        un-acked, retransmit the stored bytes and back the timer off
        exponentially; after ``max_retransmits`` attempts give up and
        escalate — the pair's next send becomes a forced table-bearing
        full sync that resynchronizes everything the lost packets
        carried (and anything else that moved since)."""
        j, pseq, attempt, interval = payload
        entry = self._pending.get(pseq)
        if entry is None:
            return  # acked in time (or churn purged the pair)
        if not (self._active[i] and self._active[j]):
            self._pending.pop(pseq, None)
            return
        t = self.transport
        if attempt > int(getattr(t, "max_retransmits", 0)):
            self._pending.pop(pseq, None)
            pair = self._pairs.get((i, j))
            if pair is not None:
                pair.sync_round = None
            self.stats.sync_escalations += 1
            return
        buf = entry[3]
        self.stats.retransmits += 1
        self.stats.bytes_sent += len(buf)
        self._send_message(now, i, j, "packet", buf, pseq)
        if pseq in self._pending:  # not delivered+acked inline
            self._schedule_rto(
                now, i, j, pseq, attempt + 1,
                interval * float(getattr(t, "rto_backoff", 2.0)),
            )

    def _heard(self, recv: int, sender: int, now: float) -> None:
        """Feed the (receiver, sender) failure detector: any arrival —
        advert datagram, delta packet, duplicate, even a corrupted
        packet — is evidence the sender is alive. Tracked only under a
        transport model (suspicion is meaningless on a perfect
        network)."""
        if self.transport is None:
            return
        fd = self._fd.get((recv, sender))
        if fd is None:
            fd = self._fd[(recv, sender)] = _FailureDetector(
                int(getattr(self.transport, "phi_window", 16))
            )
        fd.heard(now)
        self._fd_rev += 1

    def suspicion_phi(self, recv: int, sender: int, now: float) -> float:
        """Phi-accrual suspicion of ``sender`` as seen by ``recv``:
        0.0 means just heard from (or never tracked), larger means the
        current silence is increasingly improbable given the pair's
        observed inter-arrival history."""
        fd = self._fd.get((recv, sender))
        return 0.0 if fd is None else fd.phi(now)

    def suspected_peers(self, recv: int, now: float) -> set[int]:
        """Active peers whose delivery silence toward ``recv`` pushed
        the phi-accrual detector past ``transport.phi_threshold``.
        Empty without a transport model. Only direct senders are ever
        tracked — peers whose state arrives as hearsay are covered by
        the existing per-column staleness gating instead."""
        if self.transport is None:
            return set()
        thr = float(getattr(self.transport, "phi_threshold", 8.0))
        out: set[int] = set()
        for (r, s), fd in self._fd.items():
            if (
                r == recv
                and self._active[s]
                and fd.last is not None
                and now - fd.last >= fd.suspect_gap(thr)
            ):
                out.add(s)
        return out

    def suspicion_quiet_until(self) -> float:
        """Earliest absolute time at which any tracked pair's phi can
        cross the suspicion threshold, assuming no further arrivals
        (each arrival pushes its pair's crossing out). +inf with no
        transport or no gap history. Cached per arrival history, so
        the simulator's per-event suspicion refresh can skip all work
        while ``now`` is below it and nobody is currently suspect."""
        if self.transport is None:
            return math.inf
        cache = self._susp_cache
        if cache is not None and cache[0] == self._fd_rev:
            return cache[1]
        thr = float(getattr(self.transport, "phi_threshold", 8.0))
        due = math.inf
        for fd in self._fd.values():
            if fd.last is None:
                continue
            g = fd.suspect_gap(thr)
            if math.isfinite(g):
                due = min(due, fd.last + g)
        self._susp_cache = (self._fd_rev, due)
        return due

    def suspect_mask(self, recv: int, now: float) -> Optional[np.ndarray]:
        """Boolean mask over peer ``recv``'s view columns: True where
        the column's owning peer is currently suspect. None when no
        peer is suspect — the common case, so callers can skip the
        masking work entirely."""
        suspects = self.suspected_peers(recv, now)
        if not suspects:
            return None
        bad: set[str] = set()
        for k in suspects:
            bad.update(self.peers[k].home_names)
        bad -= set(self.peers[recv].home_names)  # own homes are never hearsay
        if not bad:
            return None
        return np.asarray([n in bad for n in self.peers[recv].view.names])

    def mean_delivery_gap(self, recv: Optional[int] = None) -> Optional[float]:
        """Mean observed inter-arrival gap across failure detectors
        (optionally restricted to one receiver); None before any pair
        has two arrivals. Feeds adaptive staleness widening: when the
        transport stretches real delivery gaps past the nominal
        exchange interval, freshness expectations stretch with them."""
        gaps = [
            g
            for (r, _s), fd in self._fd.items()
            if recv is None or r == recv
            for g in (fd.mean_gap(),)
            if g is not None
        ]
        return (sum(gaps) / len(gaps)) if gaps else None

    @property
    def in_flight(self) -> int:
        """Messages in flight: a batched round's entry counts each of
        its packets (or acks)."""
        n = 0
        for e in self._in_flight:
            if e[3] == "batch":
                n += len(e[4].plan.pairs)
            elif e[3] == "batch_ack":
                acked = e[4][1]
                n += len(e[4][0].plan.pairs) if acked is None else int(acked.sum())
            else:
                n += 1
        return n

    def next_due(self) -> float:
        """Arrival time of the earliest in-flight message (advert
        payloads and, on the delta wire, acks riding back)."""
        if not self._in_flight:
            raise ValueError("no adverts in flight")
        return self._in_flight[0][0]

    # -- protocol --------------------------------------------------------------
    def _marks(self) -> tuple[int, int, int]:
        s = self.stats
        return s.deliveries, s.bytes_sent, s.adverts_applied

    def _count_since(self, marks: tuple[int, int, int]) -> None:
        """Add what the stats gained since ``marks`` to the
        ``diana.p2p.*`` counters."""
        now = self._marks()
        for name, a, b in zip(("packets", "bytes", "rows_merged"), marks, now):
            trace.count(f"diana.p2p.{name}", b - a)

    def deliver_due(self, now: float) -> int:
        """Deliver every in-flight message whose latency elapsed.
        Returns the number of advert columns applied (acks deliver too
        but count nothing here)."""
        with trace.span("diana.p2p.deliver"):
            marks = self._marks() if trace.on else None
            applied = self._deliver_due(now)
            if marks is not None:
                self._count_since(marks)
        return applied

    def _deliver_due(self, now: float) -> int:
        applied = 0
        while self._in_flight and self._in_flight[0][0] <= now:
            due, _tb, j, kind, payload = heapq.heappop(self._in_flight)
            if kind == "adverts":
                sender, adverts = payload
                if not self._active[j]:
                    continue          # receiver departed mid-flight
                self._heard(j, sender, due)
                got = self.peers[j].receive(adverts)
                self.stats.deliveries += 1
                self.stats.adverts_applied += got
                applied += got
            elif kind == "packet":
                sender, pseq, buf = payload
                if not (self._active[j] and self._active[sender]):
                    # Either end churned while the packet was airborne:
                    # the pair state was reset, so the packet (and its
                    # pending-ack entry) is void.
                    self._pending.pop(pseq, None)
                    continue
                applied += self._deliver_packet(due, sender, j, buf, pseq)
            elif kind == "rto":  # j is the retransmitting sender here
                self._fire_rto(due, j, payload)
            elif kind == "batch":
                applied += self._deliver_batch(due, payload)
            elif kind == "batch_ack":
                self._ack_batch(*payload)
            else:  # "ack" — j is the original packet's sender here
                if not self._active[j]:
                    continue
                self._apply_ack(payload)
        return applied

    def round(self, now: float) -> ExchangeStats:
        """One advertisement round: every peer re-measures its home
        rows (opening new epochs only for columns whose content
        changed) and gossips to its fan-out set — everything it knows
        on the full wire, version deltas + heartbeats on the delta
        wire. Zero-latency sends apply immediately (so adverts cascade
        through the mesh within the round); otherwise they queue until
        ``deliver_due``."""
        with trace.span("diana.p2p.round"):
            marks = self._marks() if trace.on else None
            self._round(now)
            if marks is not None:
                trace.count("diana.p2p.rounds")
                self._count_since(marks)
        return self.stats

    def _round(self, now: float) -> None:
        self.stats.rounds += 1
        for k, p in enumerate(self.peers):
            if self._active[k]:
                p.refresh_home(now)
        if self._batched and now + self.latency_s > now:
            self._round_batched(now)
            return
        for i, p in enumerate(self.peers):
            targets = self.neighbors(i, self.stats.rounds)
            if not targets:
                continue
            adverts = None
            size = 0
            for j in targets:
                if self.wire == "delta":
                    self._send_delta(i, j, now)
                else:
                    if adverts is None:
                        adverts = p.adverts()
                        size = sum(advert_wire_bytes(a) for a in adverts)
                    self.stats.adverts_sent += len(adverts)
                    self.stats.bytes_sent += size
                    self._send_message(now, i, j, "adverts", adverts)

    # -- delta wire ------------------------------------------------------------
    def _send_delta(self, i: int, j: int, now: float) -> None:
        """Encode and send one sender→receiver delta packet."""
        p = self.peers[i]
        pair = self._pair(i, j)
        full_sync = (
            pair.sync_round is None
            or self.stats.rounds - pair.sync_round >= self.full_sync_every
        )
        sendable = ~p._dirty  # speculation never travels under owner epochs
        if full_sync:
            # Join/resync: everything non-dirty, table included,
            # acked vector and owner-direct suppression both ignored.
            delta = sendable.copy()
            pair.sync_round = self.stats.rounds
            self.stats.full_syncs += 1
        else:
            suppressed = self._owner_suppress.get(
                (i, j), np.zeros(len(sendable), bool)
            )
            sendable = sendable & ~suppressed
            delta = sendable & (p.version > pair.acked)
        cols = np.flatnonzero(delta)
        # Heartbeats: unchanged columns (receiver already acked exactly
        # this epoch) whose stamp moved since we last told this receiver.
        hb = sendable & ~delta & (p.stamp > pair.hb_stamp) if not full_sync else (
            np.zeros(len(sendable), bool)
        )
        hb_cols = np.flatnonzero(hb)
        payload = encode_packet(
            names=p.view.names,
            ids=cols,
            qrows=np.stack(
                [p.view.queue[cols], p.view.work[cols], p.view.load[cols]]
            ),
            free=p.free[cols],
            alive=p.view.alive[cols],
            versions=p.version[cols],
            stamps=p.stamp[cols],
            hb_ids=hb_cols,
            hb_versions=p.version[hb_cols],
            hb_stamps=p.stamp[hb_cols],
            quant=self.quant,
            include_table=full_sync,
            pair_seq=pair.send_seq,
        )
        pair.send_seq += 1
        pair.hb_stamp[cols] = p.stamp[cols]
        pair.hb_stamp[hb_cols] = p.stamp[hb_cols]
        seq = next(self._seq)
        self._pending[seq] = ((i, j), cols, p.version[cols].copy(), payload)
        self.stats.adverts_sent += len(cols)
        self.stats.heartbeats_sent += len(hb_cols)
        self.stats.bytes_sent += len(payload)
        self._send_message(now, i, j, "packet", payload, seq, tiebreak=seq)
        t = self.transport
        if (
            t is not None
            and getattr(t, "can_lose", True)
            and seq in self._pending
        ):
            # Packet not delivered+acked inline: arm its ack-timeout.
            self._schedule_rto(now, i, j, seq, 1, self._rto_initial())

    def _round_plan(self, rnd: int) -> _RoundPlan:
        """This round's ``_RoundPlan``, reused while no peer has left or
        joined (without a fan-out cap the pairs do not rotate)."""
        key = len(self._churn)
        plan = self._plan
        if plan is not None and plan.key == key and self.fanout is None:
            return plan
        N, S = len(self.peers), self._V.shape[1]
        pairs = [(i, j) for i in range(N) for j in self.neighbors(i, rnd)]
        for i, j in pairs:
            self._pair(i, j)
        I = np.fromiter((i for i, _ in pairs), np.int64, len(pairs))
        J = np.fromiter((j for _, j in pairs), np.int64, len(pairs))
        K = I * N + J
        r, c = np.nonzero(~self._supp_rows[K])
        ic, at = I[r] * S + c, J[r] * S + c
        pm = np.zeros((N, N), bool)
        pm[I, J] = True
        self._plan = _RoundPlan(
            key, pairs, I, J, K, r, c, ic, K[r] * S + c, at,
            len(np.unique(at)) == len(at), bool(self._HC.ravel()[ic].all()), pm,
        )
        return self._plan

    def _round_batched(self, now: float) -> None:
        """``_send_delta`` for every pair of the round at once.

        Each packet's advertised columns, heartbeats, full sync, wire
        bytes and sequence numbers are what ``_send_delta`` gives; the
        packets are not serialized (their sizes follow
        ``encode_packet``'s layout) and travel as one heap entry that
        holds what each carries."""
        rnd = self.stats.rounds
        plan = self._round_plan(rnd)
        P = len(plan.pairs)
        if not P:
            return
        st, K = self._store, plan.K
        rev = len(self._churn)
        if rnd < plan.next_full:
            full = np.zeros(P, bool)
            batch = self._send_steady(plan, full, rev) or self._send_entries(plan, full, rev)
        else:
            last = st.sync_round[K]
            full = (last < 0) | (rnd - last >= self.full_sync_every)
            if full.all():
                batch = self._send_full(plan, full, rev)
            else:
                batch = self._send_entries(plan, full, rev)
            st.sync_round[K[full]] = rnd
            last = st.sync_round[K]
            plan.next_full = -1 if (last < 0).any() else int(last.min()) + self.full_sync_every
            self.stats.full_syncs += int(full.sum())
        batch.pair_seqs = st.send_seq[K] & 0xFFFFFFFF
        st.send_seq[K] += 1
        tiebreak = next(self._seq)
        self._seq = itertools.count(tiebreak + P)  # one seq per packet
        heapq.heappush(
            self._in_flight, (now + self.latency_s, tiebreak, -1, "batch", batch)
        )

    def _wire_bytes(self, P: int, n: np.ndarray, n_hb: int, n_full: int) -> int:
        """Bytes of ``P`` packets as ``encode_packet`` lays them out,
        ``n`` advertised columns each, ``n_hb`` heartbeats in all,
        ``n_full`` of them carrying the site-id table."""
        id_b = 4 if self._V.shape[1] > 0xFFFF else 2
        q_b = np.dtype(_QUANT_DTYPES[self.quant]).itemsize
        out = (
            P * (2 + _HEADER.size + _CRC.size)
            + int(n.sum()) * (id_b + 16 + (len(QUANT_FIELDS) + 1) * q_b)
            + int(((n + 7) // 8).sum()) + n_hb * (id_b + 16)
        )
        if n_full:
            if self._table_b is None:
                self._table_b = 0
                for name in self.peers[0].view.names:
                    b = name.encode("utf-8")
                    if len(b) > 255:
                        self._table_b = None
                        raise ValueError(f"site name too long for wire: {name!r}")
                    self._table_b += 1 + len(b)
            out += n_full * self._table_b
        return out

    def _wire_rows(self) -> tuple:
        """Every peer's advertised values as the wire delivers them:
        (queue, work, load, free slots) dequantized, and liveness."""
        dt = _QUANT_DTYPES[self.quant]
        return tuple(
            x.astype(dt).astype(np.float64) for x in (self._Q, self._W, self._L, self._F)
        ) + (self._A.copy(),)

    def _send_full(self, plan: _RoundPlan, full, rev: int) -> _RoundBatch:
        """A round where every pair full-syncs: each packet carries
        every column its sender has not speculated on (``send``, one
        row per sender), over the (sender, receiver, column) cube."""
        N, S = self._V.shape
        send = ~self._D
        shipped = self._store.hb_stamp.reshape(N, N, S)
        np.copyto(shipped, self._T[:, None, :], where=plan.pm[:, :, None] & send[:, None, :])
        n = send.sum(1)[plan.I]
        self.stats.adverts_sent += int(n.sum())
        self.stats.bytes_sent += self._wire_bytes(len(plan.K), n, 0, len(plan.K))
        self._quiet = None
        return _RoundBatch(
            plan, full, _NONE, rev,
            dense=(send, self._V.copy(), self._T.copy(), self._wire_rows()),
        )

    def _send_steady(self, plan: _RoundPlan, full, rev: int) -> Optional[_RoundBatch]:
        """A heartbeat round replayed from the plan, or None when it
        would not be one: the epochs are those of the last round that
        advertised nothing (so, acks only growing, nothing is to
        advertise), every entry is a column its sender owns (never
        speculated on), and every entry's stamp moved since it last
        shipped — then every entry is a heartbeat."""
        q = self._quiet
        if (
            q is None or q[0] is not plan or not plan.home_only
            or not np.array_equal(self._V, q[1])
        ):
            return None
        shipped = self._store.hb_stamp.ravel()
        t = self._T.ravel()[plan.ic]
        if not (t > shipped[plan.kc]).all():
            return None
        shipped[plan.kc] = t
        self.stats.heartbeats_sent += len(t)
        self.stats.bytes_sent += q[3]
        return _RoundBatch(
            plan, full, _NONE, rev, unique=plan.unique, steady=True,
            h_r=plan.r, h_c=plan.c, h_v=q[2], h_t=t,
        )

    def _send_entries(self, plan: _RoundPlan, full, rev: int) -> _RoundBatch:
        """Any other round, entry by entry: a pair that full-syncs
        carries every column (suppression ignored), any other the
        plan's entries whose epoch its receiver has not acknowledged
        (advertised) or whose stamp moved since it last shipped
        (heartbeats); neither travels where the sender speculated."""
        st, S = self._store, self._V.shape[1]
        r, c, unique = plan.r, plan.c, plan.unique
        if full.any():
            fr = np.flatnonzero(full)
            keep = ~full[r]
            r = np.concatenate([r[keep], np.repeat(fr, S)])
            c = np.concatenate([c[keep], np.tile(np.arange(S), len(fr))])
            order = np.argsort(r, kind="stable")
            r, c, unique = r[order], c[order], False
        ic = plan.I[r] * S + c
        kc = plan.K[r] * S + c
        v = self._V.ravel()[ic]
        t = self._T.ravel()[ic]
        acked, shipped = st.acked.ravel(), st.hb_stamp.ravel()
        send = ~self._D.ravel()[ic]
        fe = full[r]
        delta = send & (fe | (v > acked[kc]))
        hb = send & ~delta & ~fe & (t > shipped[kc])
        told = delta | hb
        shipped[kc[told]] = t[told]
        d, h = np.flatnonzero(delta), np.flatnonzero(hb)
        content = None
        if len(d):
            content = tuple(x.ravel()[ic[d]] for x in self._wire_rows())
        self.stats.adverts_sent += len(d)
        self.stats.heartbeats_sent += len(h)
        self.stats.bytes_sent += self._wire_bytes(
            len(plan.K), np.bincount(r[d], minlength=len(plan.K)), len(h),
            int(full.sum()),
        )
        if full.any() or len(d):
            self._quiet = None
        else:
            self._quiet = (
                plan, self._V.copy(), self._V.ravel()[plan.ic],
                self._wire_bytes(len(plan.K), _NONE, len(plan.ic), 0),
            )
        return _RoundBatch(
            plan, full, _NONE, rev, unique=unique,
            d_r=r[d], d_c=c[d], d_v=v[d], d_t=t[d], d_content=content,
            h_r=r[h], h_c=c[h], h_v=v[h], h_t=t[h],
        )

    def _deliver_batch(self, now: float, b: _RoundBatch) -> int:
        """``_deliver_packet`` for every packet of one batched round, in
        send order. Returns the columns applied.

        Until a peer leaves or joins, every packet reaches the pair
        state it left, in order; after, each is checked as
        ``_deliver_due`` and ``_deliver_packet`` check a packet."""
        plan, st = b.plan, self._store
        P = len(plan.pairs)
        every = None  # fresh and acknowledged: every packet, else masks
        fresh = acked = None
        if len(self._churn) == b.rev:
            if b.full.any():
                names = list(self.peers[0].view.names)
                for k in np.flatnonzero(b.full):
                    st.table[plan.K[k]] = names
            # In order (the common case): the window shifts by one.
            K, s = plan.K, b.pair_seqs
            top = st.recv_max[K]
            easy = (top < 0) | (s == top + 1)
            rows = K if (every := bool(easy.all())) else K[easy]
            st.recv_window[rows] = np.where(
                top[easy] < 0, np.uint64(0), (st.recv_window[rows] << np.uint64(1)) | np.uint64(1)
            )
            st.recv_max[rows] = s[easy]
            if not every:
                fresh, acked = easy.copy(), easy.copy()
                for k in np.flatnonzero(~easy):
                    self._accept(b, k, self._pairs[plan.pairs[k]], fresh, acked)
        else:
            moved = set(self._churn[b.rev:])
            fresh, acked = np.zeros(P, bool), np.zeros(P, bool)
            for k, (i, j) in enumerate(plan.pairs):
                if not (self._active[j] and self._active[i]):
                    continue
                pair = self._pair(i, j)
                if b.full[k]:
                    pair.table = list(self.peers[0].view.names)
                if pair.table is None:
                    continue
                self._accept(b, k, pair, fresh, acked)
        n_fresh = P if every else int(fresh.sum())
        n_ack = P if every else int(acked.sum())
        applied = 0
        if b.steady and every:
            self._refresh_steady(b)
        elif n_fresh:
            self._steady = None
            got = self._apply_full(b) if b.dense is not None and every else None
            if got is None:
                got = self._apply_batch(b, np.ones(P, bool) if every else fresh)
            applied = got
        self.stats.deliveries += n_fresh
        self.stats.adverts_applied += applied
        self.stats.acks_sent += n_ack
        self.stats.bytes_sent += ACK_WIRE_BYTES * n_ack
        if n_ack:
            tiebreak = next(self._seq)
            self._seq = itertools.count(tiebreak + n_ack)  # one seq per ack
            if every:
                acked = None
            due = now + self.latency_s
            if due <= now:
                self._ack_batch(b, acked)
            else:
                heapq.heappush(
                    self._in_flight, (due, tiebreak, -1, "batch_ack", (b, acked))
                )
        return applied

    def _accept(self, b, k, pair, fresh, acked) -> None:
        """One packet's replay-window check (``accept_seq``): it is
        acknowledged either way, merged only when fresh."""
        ok, reordered = pair.accept_seq(int(b.pair_seqs[k]))
        if reordered:
            self.stats.reordered += 1
        if not ok:
            self.stats.dup_suppressed += 1
        acked[k] = True
        fresh[k] = ok

    def _refresh_steady(self, b: _RoundBatch) -> None:
        """A steady heartbeat round's ``refresh_stamps``. When the
        receivers' epochs and speculation are those of the last steady
        delivery, and that one refreshed every entry not ruled out by
        them, the same entries refresh now (their stamps were that
        round's, older than this one's)."""
        c = self._steady
        if (
            c is not None and c[0] is b.plan
            and np.array_equal(self._V, c[1]) and np.array_equal(self._D, c[2])
        ):
            self._T.ravel()[c[3]] = b.h_t[c[4]]
            return
        at = b.plan.at
        T = self._T.ravel()
        fit = ~self._HC.ravel()[at] & ~self._D.ravel()[at] & (self._V.ravel()[at] == b.h_v)
        ok = fit & (b.h_t > T[at])
        T[at[ok]] = b.h_t[ok]
        self._steady = (
            (b.plan, self._V.copy(), self._D.copy(), at[ok], np.flatnonzero(ok))
            if (ok == fit).all() else None
        )

    def _apply_full(self, b: _RoundBatch) -> Optional[int]:
        """Every packet of an all-pairs full sync, at once, when none
        carries an epoch newer than its receiver's or one the receiver
        speculated on: then nothing applies and each receiver keeps the
        freshest stamp sent for an epoch it holds (``merge_packed_rows``
        touches). None otherwise."""
        send, V, T, _ = b.dense
        mine = b.plan.pm[:, :, None] & send[:, None, :] & ~self._HC[None, :, :]
        v, held = V[:, None, :], self._V[None, :, :]
        if (mine & (v > held)).any():
            return None
        equal = mine & (v == held)
        if (equal & self._D[None, :, :]).any():
            return None
        np.maximum(self._T, np.where(equal, T[:, None, :], -np.inf).max(axis=0), out=self._T)
        return 0

    def _entries(self, b: _RoundBatch) -> None:
        """Fill a dense batch's advertised entries."""
        send, V, T, rows = b.dense
        r, c = np.nonzero(send[b.plan.I])
        i = b.plan.I[r]
        b.d_r, b.d_c, b.d_v, b.d_t = r, c, V[i, c], T[i, c]
        b.d_content = tuple(x[i, c] for x in rows)

    def _apply_batch(self, b: _RoundBatch, fresh: np.ndarray) -> int:
        """``receive_packed`` then ``refresh_stamps`` for the fresh
        packets of a round, over the exchange's (N, S) arrays: at once
        when no (receiver, column) is carried twice, else sender by
        sender in send order (one sender's packets go to distinct
        receivers). Returns the columns applied."""
        if b.dense is not None and not len(b.d_r):
            self._entries(b)
        S = self._V.shape[1]
        J = b.plan.J
        dm, hm = fresh[b.d_r], fresh[b.h_r]
        d_r, h_r = b.d_r[dm], b.h_r[hm]
        d_at, h_at = J[d_r] * S + b.d_c[dm], J[h_r] * S + b.h_c[hm]
        dv, dt, hv, ht = b.d_v[dm], b.d_t[dm], b.h_v[hm], b.h_t[hm]
        if b.unique:
            put = self._merge_entries(d_at, dv, dt)
            self._refresh_entries(h_at, hv, ht)
            puts = [(d_at[put], np.flatnonzero(dm)[put])]
        else:
            I = b.plan.I
            d_i, h_i = I[d_r], I[h_r]
            puts = []
            for i in np.unique(np.concatenate([d_i, h_i])):
                ds, hs = d_i == i, h_i == i
                put = self._merge_entries(d_at[ds], dv[ds], dt[ds])
                puts.append((d_at[ds][put], np.flatnonzero(dm)[ds][put]))
                self._refresh_entries(h_at[hs], hv[hs], ht[hs])
        at = np.concatenate([p[0] for p in puts])
        if not len(at):
            return 0
        src = np.concatenate([p[1] for p in puts])
        applied = len(src)
        if not b.unique:
            # A (receiver, column) merged twice keeps the later content.
            last = len(at) - 1 - np.unique(at[::-1], return_index=True)[1]
            at, src = at[last], src[last]
        for x, vals in zip((self._Q, self._W, self._L, self._F, self._A), b.d_content):
            x.ravel()[at] = vals[src]
        return applied

    def _merge_entries(self, at, v, t) -> np.ndarray:
        """``merge_packed_rows`` on flat (receiver, column) entries
        ``at``, none twice: a strictly newer epoch applies, or an equal
        one over the receiver's own speculation; an equal epoch with a
        fresher stamp refreshes the stamp; home columns are protected.
        Returns which entries applied."""
        V, T, D = self._V.ravel(), self._T.ravel(), self._D.ravel()
        mine = ~self._HC.ravel()[at]
        held = V[at]
        equal = mine & (v == held)
        put = (mine & (v > held)) | (equal & D[at])
        touch = equal & ~put & (t > T[at])
        T[at[put]] = np.maximum(T[at[put]], t[put])
        T[at[touch]] = t[touch]
        V[at[put]] = v[put]
        D[at[put]] = False
        return put

    def _refresh_entries(self, at, v, t) -> None:
        """``refresh_stamps`` on flat (receiver, column) entries, none
        twice: the echoed epoch held, not home, not speculated, and a
        fresher stamp."""
        T = self._T.ravel()
        ok = (
            ~self._HC.ravel()[at] & ~self._D.ravel()[at]
            & (self._V.ravel()[at] == v) & (t > T[at])
        )
        T[at[ok]] = t[ok]

    def _ack_batch(self, b: _RoundBatch, acked: np.ndarray) -> None:
        """``_apply_ack`` for the acknowledgements of one batched round
        (``acked`` None: every packet): a pair touched by a leave or
        join since the send has no pending packet left to acknowledge."""
        if b.dense is None and not len(b.d_r):
            return  # no column was advertised: nothing to advance
        if acked is None:
            acked = np.ones(len(b.plan.pairs), bool)
        if len(self._churn) > b.rev:
            moved = set(self._churn[b.rev:])
            acked = acked & np.asarray(
                [not (i in moved or j in moved) for i, j in b.plan.pairs]
            )
        if b.dense is not None:
            send, V = b.dense[0], b.dense[1]
            N, S = V.shape
            ok = np.zeros((N, N), bool)
            ok[b.plan.I[acked], b.plan.J[acked]] = True
            A = self._store.acked.reshape(N, N, S)
            np.copyto(A, np.maximum(A, V[:, None, :]), where=ok[:, :, None] & send[:, None, :])
            return
        m = acked[b.d_r]
        at = b.plan.K[b.d_r[m]] * self._V.shape[1] + b.d_c[m]
        A = self._store.acked.ravel()
        A[at] = np.maximum(A[at], b.d_v[m])

    def _deliver_packet(
        self, now: float, sender: int, j: int, buf: bytes, seq: int
    ) -> int:
        """Decode one delta packet at receiver ``j``, merge it, and send
        the acknowledgement back (it rides the same latency heap and
        the same faulty transport). Corrupted packets — checksum
        mismatch or otherwise undecodable bytes — are dropped un-acked;
        the sender's retransmit timer recovers them. The per-pair
        replay window suppresses duplicates (still acked, so the
        sender's timer stands down) and counts reordered arrivals,
        which merge as normal: every merge path is version-gated, so a
        stale reordered column is a no-op."""
        self._heard(j, sender, now)
        try:
            pkt = decode_packet(buf)
        except PacketError:
            self.stats.corrupted += 1
            return 0
        pair = self._pair(sender, j)
        if pkt["table"] is not None:
            pair.table = list(pkt["table"])
        if pair.table is None:
            # No interned site-id table for this pair: churn reset it
            # after the packet was sent (a pre-churn delta raced the
            # rejoin). The ids are meaningless without the table, so
            # drop the packet un-acked — the forced full sync on the
            # pair's next send resynchronizes everything it carried.
            self._pending.pop(seq, None)
            return 0
        fresh, reordered = pair.accept_seq(pkt["pair_seq"])
        if reordered:
            self.stats.reordered += 1
        if not fresh:
            # Duplicate: a transport-injected copy or a retransmission
            # racing its own ack. Don't re-merge, but re-ack so the
            # sender stops retransmitting.
            self.stats.dup_suppressed += 1
            self.stats.acks_sent += 1
            self.stats.bytes_sent += ACK_WIRE_BYTES
            self._send_message(now, j, sender, "ack", seq)
            return 0
        names = pair.table
        recv = self.peers[j]
        self._steady = None
        applied = recv.receive_packed(
            names=[names[c] for c in pkt["ids"]],
            qrows=pkt["rows"],
            free=pkt["free"],
            alive=pkt["alive"],
            versions=pkt["versions"],
            stamps=pkt["stamps"],
        )
        recv.refresh_stamps(
            names=[names[c] for c in pkt["hb_ids"]],
            versions=pkt["hb_versions"],
            stamps=pkt["hb_stamps"],
        )
        self.stats.deliveries += 1
        self.stats.adverts_applied += applied
        self.stats.acks_sent += 1
        self.stats.bytes_sent += ACK_WIRE_BYTES
        self._send_message(now, j, sender, "ack", seq)
        return applied

    def _apply_ack(self, seq: int) -> None:
        """The receiver holds everything packet ``seq`` advertised:
        advance the sender's per-receiver acked version vector. Acks
        whose pending entry or pair state was purged by churn are
        no-ops (the reset pair restarts from a full sync anyway)."""
        entry = self._pending.pop(seq, None)
        if entry is None:
            return
        (i, j), cols, versions = entry[0], entry[1], entry[2]
        pair = self._pairs.get((i, j))
        if pair is None:
            return
        pair.acked[cols] = np.maximum(pair.acked[cols], versions)
