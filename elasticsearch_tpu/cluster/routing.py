"""Operation routing: doc → shard, search shard selection.

Analogue of cluster/routing/operation/plain/PlainOperationRouting.java (SURVEY.md §2.2):
shard_id = djb2(routing ?: id) % num_shards — the exact DJB2 hash
(hash/djb/DjbHashFunction.java:28), because shard placement of every document depends on
it and it is frozen at index creation (hash stability).

searchShards picks ONE copy per replication group honoring `preference`
(_primary/_local/_only_node:x/session key). Preference-free selection is
ADAPTIVE when a `cluster.stats.AdaptiveReplicaSelector` is wired (the node
does): copies are ranked by the C3-style health score (latency EWMA,
piggybacked queue depth/breaker headroom, outstanding attempts, decayed
failures) with round-robin rotation among the healthy set, falling back to
plain round-robin until the group's stats warm up (min_samples per copy).

`_local`/`_prefer_node` with NO matching copy fall back to that same
selection — NOT to hashing the preference string itself, which would send
every coordinator to the SAME deterministic copy index (the hotspot bug:
djb2("_local") is a constant, so a 3-copy group with no local copy had all
of its cluster-wide traffic pinned to one copy).
"""

from __future__ import annotations

import itertools

from ..common.errors import IndexShardMissingError, NoShardAvailableError
from .state import ClusterState, IndexShardRoutingTable, ShardRouting


def djb2_hash(value: str) -> int:
    """DJB2 exactly as the reference computes it (32-bit overflow semantics)."""
    h = 5381
    for ch in value:
        h = ((h << 5) + h + ord(ch)) & 0xFFFFFFFF
    # Java int is signed; modulo uses absolute value downstream
    if h >= 0x80000000:
        h -= 0x100000000
    return h


class OperationRouting:
    def __init__(self, selector=None):
        self._rr = itertools.count()
        # AdaptiveReplicaSelector (cluster/stats.py) or None = always RR
        self.selector = selector

    @staticmethod
    def shard_id(state: ClusterState, index: str, doc_id: str,
                 routing: str | None = None) -> int:
        meta = state.metadata.require_index(index)
        h = djb2_hash(str(routing) if routing is not None else str(doc_id))
        return abs(h) % meta.number_of_shards

    def index_shard(self, state: ClusterState, index: str, doc_id: str,
                    routing: str | None = None) -> IndexShardRoutingTable:
        table = state.routing_table.index(index)
        if table is None:
            raise IndexShardMissingError(f"no routing for index [{index}]")
        return table.shard(self.shard_id(state, index, doc_id, routing))

    def get_shard_copy(self, state: ClusterState, index: str, doc_id: str,
                       routing: str | None = None,
                       preference: str | None = None) -> ShardRouting:
        """A single active copy for reads (get/explain — single-shard pattern)."""
        group = self.index_shard(state, index, doc_id, routing)
        return self._select(group, state, preference)

    @staticmethod
    def split_preference(preference: str | None) \
            -> tuple[set[int] | None, str | None]:
        """Parse the preference grammar's compound form: "_shards:0,2[;pref]"
        restricts the searched shard groups, with an optional ";" suffix
        carrying the copy-selection preference (ref: Preference.SHARDS
        handling in PlainOperationRouting). The ONE parser for this shape —
        search_shards and the coordinator's hedge gate both route here, so
        the grammar cannot drift between them."""
        if not preference or not preference.startswith("_shards:"):
            return None, preference or None
        rest = preference[len("_shards:"):]
        spec, _, copy_pref = rest.partition(";")
        return ({int(s) for s in spec.split(",") if s.strip()},
                copy_pref or None)

    def search_shards(self, state: ClusterState, indices: list[str],
                      routing: str | None = None,
                      preference: str | None = None,
                      affinity: str | None = None) -> list[ShardRouting]:
        """One active copy of every relevant shard group (ref: searchShards:103-146).

        `affinity` is the request-cache fingerprint of a cache-eligible
        request (actions passes it; None otherwise): a SOFT rendezvous
        affinity applied inside preference-free selection so the same hot
        query lands on the same healthy copy and replica request caches
        partition instead of duplicating. Health still dominates (the
        affinity pick happens within the adaptive spread set), probes and
        quarantine are unchanged, and every explicit preference wins."""
        only_shards, preference = self.split_preference(preference)
        out = []
        for index in indices:
            table = state.routing_table.index(index)
            if table is None:
                continue
            meta = state.metadata.require_index(index)
            if routing is not None:
                shard_ids = {abs(djb2_hash(r)) % meta.number_of_shards
                             for r in str(routing).split(",")}
            else:
                shard_ids = range(len(table.shards))
            for sid in shard_ids:
                if only_shards is not None and sid not in only_shards:
                    continue
                group = table.shard(sid)
                out.append(self._select(group, state, preference,
                                        affinity=affinity))
        return out

    def _select(self, group: IndexShardRoutingTable, state: ClusterState,
                preference: str | None,
                affinity: str | None = None) -> ShardRouting:
        active = group.active_shards()
        if not active:
            raise NoShardAvailableError(
                f"no active copy for [{group.shards[0].index}][{group.shards[0].shard_id}]"
                if group.shards else "empty shard group"
            )
        if preference:
            if preference == "_primary":
                for s in active:
                    if s.primary:
                        return s
                raise NoShardAvailableError("primary not active")
            if preference == "_local":
                if state.nodes.local_id:
                    for s in active:
                        if s.node_id == state.nodes.local_id:
                            return s
                # no local copy: fall back to adaptive/round-robin — hashing
                # the literal "_local" would pin every coordinator without a
                # copy to the SAME index (djb2 of a constant string)
                return self._pick(active, affinity)
            if preference.startswith("_only_node:"):
                node_id = preference.split(":", 1)[1]
                for s in active:
                    if s.node_id == node_id:
                        return s
                raise NoShardAvailableError(f"no copy on node [{node_id}]")
            if preference.startswith("_prefer_node:"):
                node_id = preference.split(":", 1)[1]
                for s in active:
                    if s.node_id == node_id:
                        return s
                return self._pick(active, affinity)  # _local fall-through rule
            # arbitrary session key → stable copy choice
            idx = abs(djb2_hash(preference)) % len(active)
            return active[idx]
        return self._pick(active, affinity)

    @staticmethod
    def rendezvous(affinity: str, copies: list[ShardRouting]) -> ShardRouting:
        """Highest-random-weight pick of `affinity` over `copies`: every
        coordinator computes the same winner for the same fingerprint
        (unkeyed blake2b — seed-stable across processes, unlike djb2 whose
        weak avalanche lets the node-id's LAST byte dominate and pin every
        fingerprint to one copy), and removing a copy only remaps the
        fingerprints it owned — the property that makes N replica request
        caches partition instead of duplicate."""
        import hashlib

        return max(copies, key=lambda s: (
            hashlib.blake2b(f"{affinity}#{s.node_id}".encode("utf-8"),
                            digest_size=8).digest(),
            s.node_id))

    def _pick(self, active: list[ShardRouting],
              affinity: str | None = None) -> ShardRouting:
        """Preference-free copy choice: adaptive rank rotation when the
        selector is wired AND warm for this group (the selector applies the
        affinity inside its spread set), else round-robin (which is what
        warms it) — except that a COLD group with an affinity fingerprint
        still round-robins: warming every copy's stats outranks early cache
        locality, and the affinity becomes effective the moment the group
        warms."""
        if self.selector is not None:
            s = self.selector.select(active, affinity=affinity)
            if s is not None:
                return s
            if self.selector.enabled and len(active) > 1:
                return active[next(self._rr) % len(active)]
        if affinity is not None and len(active) > 1:
            # selector-less embedding: pure rendezvous affinity (no health
            # signal exists to dominate it)
            return self.rendezvous(affinity, active)
        return active[next(self._rr) % len(active)]

    def ranked_copies(self, group: IndexShardRoutingTable,
                      first: ShardRouting) -> list[ShardRouting]:
        """Failover-chain order for one replication group: the already-chosen
        `first` copy, then the remaining active copies best-first by the
        adaptive rank (quarantined copies last) — the first fallback is the
        best REMAINING copy, not the next array slot."""
        rest = self.other_copies(group, first)
        if self.selector is not None and rest:
            rest = self.selector.ranked(rest)
        return [first] + rest

    @staticmethod
    def other_copies(group: IndexShardRoutingTable,
                     first: ShardRouting) -> list[ShardRouting]:
        """The group's active copies on other nodes than `first`'s, unranked:
        what a failover or a hedge could go to. Empty for the only copy."""
        return [s for s in group.active_shards() if s.node_id != first.node_id]
