"""Storage clients: one transport, one request book, two routings.

* :class:`ShardConnection` is THE transport: it opens one PEP-terminated
  flow to one server (the SYN), numbers and checksum-stamps every packet,
  and drains that flow's responses.
* :class:`RequestBook` is THE request tracking both clients share: rid
  allocation and outstanding counts, issue-tick stamps and end-to-end
  latency, replay notes, tick timeouts with doubling backoff, epoch-fence
  redirect replay (capped per rid), terminal ``E_SHED`` resolution, shed
  retry with jittered backoff, and the duplicate-response drop.
* :class:`DDSClient` is the book over ONE connection to one
  :class:`DDSStorageServer`; it sends at once (every issue call flushes).
* :class:`ClusterClient` is the book over one connection per shard of a
  :class:`DDSCluster`, routed through the cluster's placement, with the two
  client-side techniques of the paper's benchmark driver (§8.1):

    - **batching** — requests destined for the same shard are packed into
      one network message (``encode_batch``), so the traffic director runs
      its signature + predicate once per batch, not once per request;
    - **pipelining** — the client keeps issuing batches without waiting for
      responses; each shard's offload engine preserves per-connection
      request order (Fig 13), so responses stream back in issue order.

The burst surface of every client is ``submit(ops)`` / ``harvest``; the
one-op calls (``read``, ``write``, ``send_raw``) are forms of it.
Everything is cooperatively scheduled: ``pump()`` flushes pending batches,
steps the server(s), and drains responses — tests can single-step the
whole system deterministically.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import TYPE_CHECKING, Callable

from repro.core import wire
from repro.core.dds_server import (DDSStorageServer, drain_client_flow,
                                   encode_app_read, encode_app_write,
                                   encode_batch)
from repro.core.lifecycle import ClientLatency
from repro.core.traffic import FLAG_SYN, FiveTuple, Packet
from repro.core.vector import checksum64, scalar_mix

if TYPE_CHECKING:  # import cycle: distributed.cluster imports core
    from repro.distributed.cluster import DDSCluster

# ``submit`` op spellings -> the latency/replay class ("r"/"w").
_OP_KIND = {"r": "r", "read": "r", "w": "w", "write": "w"}


@dataclass
class ClientStats:
    requests: int = 0
    batches_sent: int = 0
    messages_sent: int = 0
    responses: int = 0
    timeouts: int = 0        # tick deadlines that expired unanswered
    resends: int = 0         # requests re-sent from replay notes
    dup_responses: int = 0   # stale/duplicate wire responses discarded


class ShardConnection:
    """One PEP-terminated flow to one server (non-blocking enqueue/flush)."""

    def __init__(self, server: DDSStorageServer, ip: str, port: int,
                 tenant: int = 0):
        self.server = server
        self.flow = FiveTuple(ip, port, "10.0.0.1", server.config.server_port,
                              tenant=tenant)
        self._resp_flow = self.flow.reversed()
        self._seq = 1  # after SYN
        self._pending: list[bytes] = []
        self._rx = bytearray()
        self.arrival_order: list[int] = []   # req ids in response order
        # Ring epoch stamped on every sent packet (-1 = untagged: standalone
        # and unreplicated traffic skips the director's epoch fence).
        self.epoch = -1
        server.director.ingress.push(Packet(self.flow, 0, b"", flags=FLAG_SYN))
        server.signal()
        server.director.step()

    def enqueue(self, msg: bytes) -> None:
        self._pending.append(msg)

    def flush(self) -> int:
        """Pack all pending messages into ONE network message (batching)."""
        if not self._pending:
            return 0
        payload = encode_batch(self._pending)
        n = len(self._pending)
        self._pending.clear()
        pkt = Packet(self.flow, self._seq, payload, epoch=self.epoch)
        if self.server.director.stamp_checksums:
            pkt.csum = checksum64(payload)
        self.server.director.ingress.push(pkt)
        self._seq += len(payload)
        self.server.signal()   # client send: mark the target server runnable
        return n

    def collect(self, responses: dict[int, tuple[int, bytes]]) -> int:
        """Drain OUR flow's packets; reassemble the segmented response stream.

        The director's ``to_client`` wire is demuxed per flow, so this is an
        O(1) swap of our own queue — other clients' traffic is never
        touched."""
        return drain_client_flow(self.server.director, self._resp_flow,
                                 self._rx, responses, self.arrival_order)


class RequestBook:
    """Request tracking over a list of :class:`ShardConnection` s.

    A subclass opens the connections, sets ``timeout_ticks`` (and, to shed
    retry, ``retry_attempts``) and supplies the routing: ``_locate(fid)``
    gives ``(shard, local fid)``, ``_route_of(shard)`` the live route of a
    shard, ``_shard_for_key(key)`` a key's owner, ``_step()`` steps the
    server(s), and ``_on_redirect(hint)`` adopts the epoch a fence refusal
    names.  ``_dead`` holds shards that can never answer and ``_servers``
    the servers whose busy devices an idle wait drains.
    """

    retry_attempts = 0   # shed retries per rid: 0 = surface E_SHED at once
    _armed = False       # epoch-tagged traffic that failover can re-route
    _dead: set[int] | frozenset = frozenset()

    def __init__(self, conns: list[ShardConnection], clock):
        self.conns = conns
        self.clock = clock
        # Lossy-wire recovery: a request unanswered for ``timeout_ticks``
        # is re-sent from its replay note with doubled backoff (the
        # server-side dedup cache makes resends exactly-once).
        self._deadlines: dict[int, tuple[int, int]] = {}  # rid -> (due, attempt)
        # rid -> ("op", kind, fid, offset, arg) for fid-addressed requests
        # (MUST re-encode at replay: a promoted shard's adopted copy has a
        # different local fid) or ("raw", shard, msg, cls, key) for
        # application messages (the bytes stay valid on the new shard).
        self._replay: dict[int, tuple] = {}
        self._retries: dict[int, int] = {}        # rid -> shed retry count
        self._redirects_seen: dict[int, int] = {}  # rid -> redirect replays
        self._backoff: list[tuple[int, int]] = []  # (due tick, rid)
        self._next_rid = 1
        self._rid_shard: dict[int, int] = {}
        self._outstanding = 0          # issued, response not yet collected
        # Per-shard issued-minus-collected counts: ``poll`` harvests ONLY
        # shards with outstanding requests, and ``flush`` visits only dirty
        # (buffered-but-unsent) connections — client-side mirrors of the
        # cluster's ready-set scheduling, so idle shards cost nothing.
        self._shard_outstanding = [0] * len(conns)
        self._dirty: list[int] = []    # shard indices with pending messages
        self._dirty_flag = [False] * len(conns)
        self._lock = threading.Lock()
        self.responses: dict[int, tuple[int, bytes]] = {}
        self.stats = ClientStats()
        # End-to-end tick latency: issue stamps per rid (reads and writes
        # in separate dicts — the class is known at issue, so the drain
        # pays one dict pop).  The offloaded-vs-host split for reads lives
        # in the server-side lifecycle histograms, where it is exact.
        self._issued_r: dict[int, int] = {}
        self._issued_w: dict[int, int] = {}
        self.latency = ClientLatency()
        self._lat_pos = [0] * len(conns)  # arrival_order scan cursors

    def _grow_conns(self) -> None:
        """Open flows to servers added since construction (clusters)."""

    # -- request issue (buffered until the next flush/pump) -------------------------
    def _enqueue(self, shard: int, msg: bytes) -> None:
        self.conns[shard].enqueue(msg)
        if not self._dirty_flag[shard]:
            self._dirty_flag[shard] = True
            self._dirty.append(shard)

    def reserve_rids(self, shards: list[int], cls="r") -> list[int]:
        """Reserve one rid per target shard in ONE lock round.

        Rid range, outstanding counters and the rid->shard map are updated
        in bulk, so a pipeline round of thousands of requests skips the
        per-call lock + dict churn.  ``cls`` picks the issue-tick stamp
        class for the end-to-end latency histograms: either one 'r'/'w'
        for the whole burst, or a per-op sequence for mixed batches."""
        n = len(shards)
        if shards and max(shards) >= len(self.conns):
            self._grow_conns()
        rid_shard = self._rid_shard
        with self._lock:
            # The per-shard counters gate response harvesting (poll skips
            # shards reading 0), so their updates stay under the client
            # lock — a lost `+= 1` against a concurrent poll() decrement
            # would park a shard with a response still queued.
            first = self._next_rid
            self._next_rid += n
            self._outstanding += n
            outs = self._shard_outstanding
            rids = list(range(first, first + n))
            for rid, shard in zip(rids, shards):
                rid_shard[rid] = shard
                outs[shard] += 1
        now = self.clock.now
        if isinstance(cls, str):
            issued = self._issued_r if cls == "r" else self._issued_w
            for rid in rids:
                issued[rid] = now
        else:
            ir, iw = self._issued_r, self._issued_w
            for rid, c in zip(rids, cls):
                (ir if c == "r" else iw)[rid] = now
        self.stats.requests += n
        return rids

    def _arm_timeout(self, rid: int) -> None:
        if self.timeout_ticks:
            self._deadlines[rid] = (self.clock.now + self.timeout_ticks, 0)

    def submit(self, ops: list[tuple]) -> list[int]:
        """Issue a burst of operations; returns one handle (request id) per
        op, in order.  Harvest results with :meth:`harvest`.

        Ops are ``("r"|"read", fid, offset, nbytes)`` or
        ``("w"|"write", fid, offset, data)``; reads and writes mix freely
        in one batch.  The client's tenant binds once per connection and
        rides every flow — never passed per call."""
        locs = []
        cls = []
        for op in ops:
            cls.append(_OP_KIND[op[0]])
            locs.append(self._locate(op[1]))
        rids = self.reserve_rids([shard for shard, _ in locs], cls)
        enqueue = self._enqueue
        replay = self._replay if self._replay_on else None
        for rid, (shard, local), k, op in zip(rids, locs, cls, ops):
            if replay is not None:
                replay[rid] = ("op", k, op[1], op[2], op[3])
                self._arm_timeout(rid)
            encode = encode_app_read if k == "r" else encode_app_write
            enqueue(shard, encode(rid, local, op[2], op[3]))
        return rids

    def read(self, fid: int, offset: int, nbytes: int) -> int:
        return self.submit([("r", fid, offset, nbytes)])[0]

    def write(self, fid: int, offset: int, data: bytes) -> int:
        return self.submit([("w", fid, offset, data)])[0]

    def issue_many(self, shards: list[int],
                   build_msg: Callable[[int, int], bytes],
                   cls="r", keys: list | None = None) -> list[int]:
        """Issue application-defined messages to explicit shards: the raw
        burst path (e.g. the KV store's ``submit``).

        ``build_msg(rid, i)`` encodes the i-th message with its reserved
        request id.  Target shards follow the live route
        (``_route_of``): after a failover the ring owner's route moves to
        the promoted replica and STAYS moved — even once the old primary
        heals and rejoins as a replica, sending to it directly would
        split-brain its application state.  ``keys`` (optional, parallel
        to ``shards``) makes replays re-hash each key against the current
        ring: a resharding flip moves key OWNERSHIP without a failover."""
        route_of = self._route_of
        shards = [route_of(s) for s in shards]
        rids = self.reserve_rids(shards, cls)
        enqueue = self._enqueue
        replay = self._replay if self._replay_on else None
        for i, (rid, shard) in enumerate(zip(rids, shards)):
            msg = build_msg(rid, i)
            if replay is not None:
                replay[rid] = ("raw", shard, msg,
                               cls if isinstance(cls, str) else cls[i],
                               keys[i] if keys is not None else None)
                self._arm_timeout(rid)
            enqueue(shard, msg)
        return rids

    def send_raw(self, shard: int, build_msg: Callable[[int], bytes],
                 cls: str = "r", key: bytes | None = None) -> int:
        """One-op form of :meth:`issue_many`: ``build_msg(rid)``."""
        return self.issue_many([shard], lambda rid, _i: build_msg(rid), cls,
                               None if key is None else [key])[0]

    # -- pipelined scheduling ---------------------------------------------------------
    def flush(self) -> int:
        """Send one batched message per DIRTY connection.

        Only connections that buffered messages since the last flush are
        visited (and their servers doorbell-signaled through
        ``ShardConnection.flush``)."""
        if not self._dirty:
            return 0
        sent = 0
        dirty, self._dirty = self._dirty, []
        flags = self._dirty_flag
        for i in dirty:
            flags[i] = False
            n = self.conns[i].flush()
            if n:
                self.stats.batches_sent += 1
                self.stats.messages_sent += n
                sent += n
        return sent

    def pump(self) -> int:
        """One cooperative step: flush -> step the server(s) -> release shed
        retries and expired timeouts -> drain responses."""
        work = self.flush()
        work += self._step()
        if self._backoff:
            work += self._pump_backoff()
        if self._deadlines:
            work += self._pump_timeouts()
        return work + self.poll()

    def poll(self) -> int:
        """Drain THIS client's responses without stepping any server.

        Harvests ONLY connections with outstanding requests (the per-shard
        issued-minus-collected counters): with several clients sharing a
        16-shard cluster, a client with traffic on two shards does not
        peek the other fourteen demuxed queues on every scheduling round.
        """
        responses = self.responses
        got = 0
        outs = self._shard_outstanding
        lat_pos = self._lat_pos
        collected: list[tuple[int, int]] = []
        rid_shard = self._rid_shard
        for i, conn in enumerate(self.conns):
            if not outs[i]:
                continue
            before = len(responses)
            conn.collect(responses)
            ao = conn.arrival_order
            if len(ao) > lat_pos[i]:
                # Exactly-once at the client: a resent request can be
                # answered twice (or a healed shard can flush a stale
                # ack).  A response whose rid is no longer booked was
                # already surfaced — discard it BEFORE the outstanding
                # accounting below, or the spurious decrement would park
                # a shard with responses still owed.
                # (pop, not del: a duplicated frame can land the same
                # rid twice in one drain window.)
                for rid in ao[lat_pos[i]:]:
                    if rid not in rid_shard and \
                            responses.pop(rid, None) is not None:
                        self.stats.dup_responses += 1
                self._record_latency(ao, lat_pos[i])
                if len(ao) >= 1 << 16:
                    # Fully consumed: reset so a long-running client's
                    # arrival log cannot grow without bound.
                    conn.arrival_order = []
                    lat_pos[i] = 0
                else:
                    lat_pos[i] = len(ao)
            n = len(responses) - before
            if n:
                collected.append((i, n))
                got += n
        if got:
            # Decrement under the issue lock: `-=` is read-modify-write,
            # and racing a concurrent issuer's increment could lose one and
            # park the shard (poll would skip it forever).
            with self._lock:
                for i, n in collected:
                    outs[i] -= n
                self._outstanding -= got
            self.stats.responses += got
        return got

    def _record_latency(self, arrival_order: list, pos: int) -> None:
        """End-to-end issue->drain ticks for newly arrived responses,
        classified read/write from the issue-side stamp dicts."""
        latency = self.latency
        now = self.clock.now
        wpop = self._issued_w.pop
        rpop = self._issued_r.pop
        radd = latency.hist_for("read").add
        wadd = latency.hist_for("write").add
        for rid in arrival_order[pos:]:
            t0 = rpop(rid, None)
            if t0 is not None:
                radd(now - t0)
                continue
            t0 = wpop(rid, None)
            if t0 is not None:
                wadd(now - t0)

    def _any_terminal(self) -> bool:
        """True iff any connected server holds a terminal mark."""
        seen: set[int] = set()
        for conn in self.conns:
            lc = conn.server.lifecycle
            if id(lc) in seen:
                continue
            seen.add(id(lc))
            if lc.has_terminal():
                return True
        return False

    def _release(self, rid: int, shard: int) -> None:
        """Unbook a rid that surfaced without a wire response."""
        self._rid_shard.pop(rid, None)
        self._issued_r.pop(rid, None)
        self._issued_w.pop(rid, None)
        with self._lock:
            self._shard_outstanding[shard] -= 1
            self._outstanding -= 1

    def _check_terminal(self, rids) -> int:
        """Reconcile terminal server-side marks for ``rids``.

        A terminally marked request never gets a wire response; without
        this, ``wait`` and ``harvest`` would spin their whole iteration
        budget into a timeout heuristic.  Two mark kinds:

        ``E_SHED``
            Dropped under overload/admission — surfaced to the caller as a
            ``(E_SHED, hint)`` response (``harvest`` may then retry it
            under the bounded-backoff policy).

        ``E_REDIRECT``
            Refused by the epoch fence: the request was routed before the
            ring changed.  Replayed transparently under the adopted epoch
            with the SAME request id (at most 8 times per rid); surfaced
            terminally past the cap or with no replay note.

        Each mark is reconciled against ITS OWN shard's outstanding counter
        exactly once — the rid->shard entry is consumed on surfacing, so a
        rid can never be double-decremented (or charged against another
        tenant's connection) even if callers probe it again."""
        found = 0
        conns = self.conns
        rid_shard = self._rid_shard
        for rid in rids:
            shard = rid_shard.get(rid)
            if shard is None:
                continue
            conn = conns[shard]
            term = conn.server.lifecycle.take_terminal(conn.flow, rid)
            if term is None:
                continue
            code, hint = term
            if code == wire.E_REDIRECT:
                seen = self._redirects_seen.get(rid, 0)
                if rid in self._replay and seen < 8:
                    self._redirects_seen[rid] = seen + 1
                    self._on_redirect(hint)
                    if self._resubmit(rid):
                        found += 1
                    continue  # else _resubmit surfaced it terminally
            self.responses[rid] = (code, hint)
            self._release(rid, shard)
            found += 1
        return found

    def _replay_msg(self, rid: int, entry: tuple) -> tuple[int, bytes]:
        """Re-materialize a request against the CURRENT routing: fid-addressed
        ops re-encode (a promoted shard's adopted copy has a different local
        fid); raw application messages re-route."""
        if entry[0] == "op":
            _, kind, fid, offset, arg = entry
            shard, local = self._locate(fid)
            encode = encode_app_read if kind == "r" else encode_app_write
            return shard, encode(rid, local, offset, arg)
        _, shard, msg, _cls, key = entry
        if key is not None:
            # Key-addressed: ownership may have MOVED at a resharding
            # flip — re-hash against the current ring (the repair chain
            # only tracks failover promotions, not migrations).
            return self._shard_for_key(key), msg
        return self._route_of(shard), msg

    def _move(self, rid: int, old: int, shard: int) -> None:
        """Re-home a booked rid's per-shard count (the totals stay)."""
        if shard != old:
            with self._lock:
                self._shard_outstanding[old] -= 1
                self._shard_outstanding[shard] += 1
            self._rid_shard[rid] = shard

    def _resubmit(self, rid: int) -> bool:
        """Move a still-booked rid to its current route and re-enqueue it.

        Counters stay booked (the request never surfaced); only the
        per-shard split moves.  A rid with no replay note — or whose
        route is itself dead (unrecoverable group) — is surfaced
        terminally as ``(E_REDIRECT, epoch)`` instead, so callers see a
        retryable error rather than a hang.  Every caller adopts the
        current epoch first, so the epoch the connections now stamp is
        the one to report."""
        old = self._rid_shard.get(rid)
        if old is None:
            return False
        entry = self._replay.get(rid)
        shard = None
        if entry is not None:
            shard, msg = self._replay_msg(rid, entry)
        if shard is None or shard in self._dead:
            hint = wire.encode_redirect_hint(self.conns[0].epoch)
            self.responses[rid] = (wire.E_REDIRECT, hint)
            self._release(rid, old)
            return False
        self._move(rid, old, shard)
        self._enqueue(shard, msg)
        return True

    # -- shed retry with bounded exponential backoff ------------------------------------
    def _maybe_retry_shed(self, got: dict, pending: set) -> None:
        """Pull retryable E_SHED results back into ``pending``.

        Honors the server's ``retry_after`` hint scaled by an exponential
        per-attempt factor; after ``retry_attempts`` tries the E_SHED
        surfaces to the caller as the terminal answer."""
        if not self.retry_attempts:
            return
        for rid in list(got):
            code, hint = got[rid]
            if code != wire.E_SHED or rid not in self._replay:
                continue
            attempt = self._retries.get(rid, 0)
            if attempt >= self.retry_attempts:
                continue   # cap reached: surface the terminal error
            del got[rid]
            pending.add(rid)
            self._retries[rid] = attempt + 1
            _, retry_after = wire.decode_shed_hint(hint)
            # Deterministic per-rid jitter de-synchronizes retry storms:
            # without it every client shed in the same tick retries in
            # the same tick, re-colliding forever.  ``scalar_mix`` is a
            # pure function of (rid, attempt), so two same-seed runs
            # still pick identical deadlines.
            base = max(1, retry_after) << attempt
            jitter = scalar_mix(rid ^ (attempt << 56)) % base
            self._backoff.append((self.clock.now + base + jitter, rid))

    def _pump_backoff(self) -> int:
        """Re-issue shed retries whose backoff deadline passed."""
        now = self.clock.now
        due = [rid for t, rid in self._backoff if t <= now]
        if not due:
            return 0
        self._backoff = [(t, rid) for t, rid in self._backoff if t > now]
        n = 0
        for rid in due:
            if self._rebook(rid):
                n += 1
        return n

    def _rebook(self, rid: int) -> bool:
        """Re-book a fully surfaced rid (counters were released when the
        E_SHED surfaced) and re-issue it along the current route."""
        entry = self._replay.get(rid)
        if entry is None:
            return False
        shard, msg = self._replay_msg(rid, entry)
        if shard in self._dead:
            return False
        cls = entry[1] if entry[0] == "op" else entry[3]
        with self._lock:
            self._outstanding += 1
            self._shard_outstanding[shard] += 1
        self._rid_shard[rid] = shard
        # Re-stamp the issue tick: the latency histogram records this
        # attempt's issue->drain, not time spent parked in backoff.
        issued = self._issued_r if cls == "r" else self._issued_w
        issued[rid] = self.clock.now
        self._arm_timeout(rid)   # re-booked requests regain loss protection
        self._enqueue(shard, msg)
        return True

    def _pump_timeouts(self) -> int:
        """Resend requests whose tick deadline expired unanswered.

        The resend re-materializes the request against the CURRENT routing
        (same request id — the server-side dedup cache suppresses the copy
        if the original survived, or replays the cached ack if only the ack
        was lost) and re-arms the deadline with doubled backoff.  Deadlines
        for answered/surfaced rids are dropped lazily here."""
        now = self.clock.now
        due = [rid for rid, (t, _a) in self._deadlines.items() if t <= now]
        if not due:
            return 0
        n = 0
        tmo = self.timeout_ticks
        for rid in due:
            if rid in self.responses or rid not in self._rid_shard:
                self._deadlines.pop(rid, None)
                continue
            entry = self._replay.get(rid)
            if entry is None:
                self._deadlines.pop(rid, None)
                continue
            attempt = self._deadlines[rid][1]
            shard, msg = self._replay_msg(rid, entry)
            if shard in self._dead:
                # Route still down: leave recovery to the failover
                # machinery, re-arm one plain window.
                self._deadlines[rid] = (now + tmo, attempt)
                continue
            self._move(rid, self._rid_shard[rid], shard)
            self._enqueue(shard, msg)
            self.stats.timeouts += 1
            self.stats.resends += 1
            self._deadlines[rid] = (now + (tmo << min(attempt + 1, 6)),
                                    attempt + 1)
            n += 1
        return n

    def _finalize(self, rid: int) -> None:
        """Drop replay/retry bookkeeping once a result reaches the caller."""
        self._rid_shard.pop(rid, None)
        self._replay.pop(rid, None)
        self._retries.pop(rid, None)
        self._redirects_seen.pop(rid, None)
        self._deadlines.pop(rid, None)

    def outstanding(self) -> int:
        """Issued-but-unanswered requests — an O(1) counter, not a dict scan."""
        return self._outstanding

    def _drain_busy_devices(self) -> None:
        """Settle device backlogs — only on servers whose device is busy."""
        for srv in self._servers:
            if srv.device.busy():
                srv.device.drain()

    def run_until_idle(self, max_iters: int = 200_000) -> None:
        """Converge on no runnable server + no outstanding requests.

        ``pump() == 0`` already certifies nothing is runnable or busy, so
        the common exit is a single zero-work round.  The bounded idle
        escape survives only for genuinely unanswerable requests."""
        idle = 0
        for _ in range(max_iters):
            if self.pump():
                idle = 0
                continue
            if self.outstanding() == 0:
                return
            self._drain_busy_devices()
            # Reconcile terminal marks: a shed or epoch-refused request
            # will never produce wire work, so without this the
            # outstanding counters stay elevated forever and idle
            # convergence always burns the full 8-round escape hatch.
            if self._check_terminal(list(self._rid_shard)):
                continue
            if self._armed and any(s in self._dead
                                   for s in set(self._rid_shard.values())):
                # Requests parked on a crashed shard are not unanswerable —
                # the supervisor will promote a replica within its timeout;
                # keep pumping so detection and replay can run.
                idle = 0
                continue
            idle += 1
            if idle >= 8:
                return  # idle with requests genuinely unanswerable
        raise TimeoutError("client did not go idle")

    # -- response access ----------------------------------------------------------------
    def wait(self, rid: int, max_iters: int = 200_000) -> tuple[int, bytes]:
        for _ in range(max_iters):
            if rid in self.responses:
                self._finalize(rid)
                return self.responses.pop(rid)
            if self.pump() == 0:
                self._drain_busy_devices()
                self._check_terminal((rid,))   # answered terminally
        raise TimeoutError(f"no response for request {rid}")

    def harvest(self, handles=None, block: bool = True,
                max_iters: int = 200_000) -> dict[int, tuple[int, bytes]]:
        """Collect responses: ``{handle: (status, body)}``.

        ``handles=None`` drains whatever has already arrived (one poll;
        never steps a server).  With explicit handles and ``block=True``
        this waits for ALL of them, harvesting whichever completes first:
        it pumps once per iteration while collecting every arrived handle —
        a serial per-handle ``wait`` loop would head-of-line block on the
        first one even when later handles (on other shards) had long
        completed.  Requests a server shed resolve terminally as
        ``(wire.E_SHED, hint)``, where the hint decodes with
        :func:`repro.core.wire.decode_shed_hint`."""
        if handles is None:
            self.poll()
            out = dict(self.responses)
            for rid in out:
                self._finalize(rid)
            self.responses.clear()
            return out
        got: dict[int, tuple[int, bytes]] = {}
        pending = set(handles)
        pending -= self._harvest(pending, got)
        if not block:
            self.poll()
            self._check_terminal(pending)
            pending -= self._harvest(pending, got)
            for rid in got:
                self._finalize(rid)
            return got
        for _ in range(max_iters):
            if not pending:
                for rid in got:
                    self._finalize(rid)
                return {rid: got[rid] for rid in handles}  # caller's order
            if self.pump() == 0:
                self._drain_busy_devices()
                self._check_terminal(pending)
            elif self._any_terminal():
                # Epoch-fence refusals can land while the servers stay
                # busy for a long stretch (a live migration pumps work
                # through its whole cleanup grace).  Waiting for the
                # pump to go idle would stall the transparent replay
                # until retirement — reconcile as soon as any terminal
                # mark exists.  The probe is O(conns), so the common
                # no-terminal iteration stays cheap.
                self._check_terminal(pending)
            pending -= self._harvest(pending, got)
            if self.retry_attempts:
                self._maybe_retry_shed(got, pending)
        raise TimeoutError(f"no response for requests {sorted(pending)[:8]}...")

    def _harvest(self, pending: set[int],
                 got: dict[int, tuple[int, bytes]]) -> set[int]:
        """Move every already-answered rid out of ``self.responses``."""
        done = pending & self.responses.keys()
        for rid in done:
            got[rid] = self.responses.pop(rid)
            self._rid_shard.pop(rid, None)
        return done


class DDSClient(RequestBook):
    """A compute-server client of one storage server: the book over ONE
    connection, sending at once (every issue call flushes it).

    ``tenant`` binds once per connection: every request rides a flow
    carrying that tenant id, which the server's QoS layer (weighted-fair
    demux, token-bucket admission, per-tenant stats) keys on.  ``epoch``
    is stamped on every packet: -1 (the default) is epoch-unaware, and the
    director accepts untagged packets unconditionally.  An epoch >= 0 or a
    ``timeout_ticks`` keeps a replay note per request, so an E_REDIRECT or
    a lost frame is answered by resending the SAME request id.
    """

    def __init__(self, server: DDSStorageServer, ip: str = "10.0.0.2",
                 port: int = 31337, tenant: int = 0,
                 timeout_ticks: int = 0):
        self.server = server
        self.tenant = tenant
        self.timeout_ticks = timeout_ticks
        self._servers = (server,)
        super().__init__([ShardConnection(server, ip, port, tenant)],
                         server.clock)

    @property
    def epoch(self) -> int:
        return self.conns[0].epoch

    @epoch.setter
    def epoch(self, value: int) -> None:
        self.conns[0].epoch = value

    @property
    def _replay_on(self) -> bool:
        return self.epoch >= 0 or self.timeout_ticks > 0

    @property
    def timeouts(self) -> int:
        return self.stats.timeouts

    @property
    def resends(self) -> int:
        return self.stats.resends

    def submit(self, ops: list[tuple]) -> list[int]:
        rids = super().submit(ops)
        self.flush()
        return rids

    def issue_many(self, shards, build_msg, cls="r", keys=None) -> list[int]:
        rids = super().issue_many(shards, build_msg, cls, keys)
        self.flush()
        return rids

    # -- routing: the one server --------------------------------------------------
    def _locate(self, fid: int) -> tuple[int, int]:
        return 0, fid

    def _route_of(self, shard: int) -> int:
        return 0

    def _shard_for_key(self, key) -> int:
        return 0

    def _step(self) -> int:
        return self.server.pump()

    def _on_redirect(self, hint: bytes) -> None:
        self.epoch = max(self.epoch, wire.decode_redirect_hint(hint))


class ClusterClient(RequestBook):
    """Batched, pipelined client for a :class:`DDSCluster`.

    Requests are routed by cluster-global file id through the cluster's
    consistent-hash placement (``cluster.locate``); applications with their
    own keys (e.g. the KV store) route via :meth:`issue_many`.  Messages
    buffer until :meth:`flush` / :meth:`pump`.
    """

    _next_base_port = 40000          # distinct flows per client by default
    _port_lock = threading.Lock()

    def __init__(self, cluster: "DDSCluster", ip: str = "10.0.0.9",
                 port: int | None = None, tenant: int = 0,
                 retry_attempts: int = 0, timeout_ticks: int = 0):
        self.cluster = cluster
        self.tenant = tenant
        self._ip = ip
        if port is None:
            # Each client needs its own source ports, or two clients' flows
            # (and therefore their responses) become indistinguishable.
            with ClusterClient._port_lock:
                port = ClusterClient._next_base_port
                ClusterClient._next_base_port += len(cluster.servers)
        conns = [ShardConnection(srv, ip, port + i, tenant)
                 for i, srv in enumerate(cluster.servers)]
        # Failover/reshard awareness, armed on replicated or elastic
        # clusters: packets are epoch-tagged, issued requests keep a replay
        # note, and an epoch bump (failover promotion or resharding flip)
        # transparently re-routes everything parked on the old owner.
        # Plain clusters pay one attribute test per pump.
        self._armed = cluster.supervisor is not None or cluster.elastic
        self._epoch_seen = cluster.epoch
        epoch = cluster.epoch if self._armed else -1
        for conn in conns:
            conn.epoch = epoch
        self.retry_attempts = retry_attempts
        self.timeout_ticks = timeout_ticks
        self._replay_on = (self._armed or retry_attempts > 0
                           or timeout_ticks > 0)
        # Live views: the cluster grows and shrinks these in place.
        self._servers = cluster.servers
        self._dead = cluster._dead
        super().__init__(conns, cluster.clock)

    def _grow_conns(self) -> None:
        """The cluster grew (elastic ``add_shard``): open flows to the new
        shards.  Ports come from the GLOBAL allocator — extending this
        client's original contiguous block would collide with whichever
        client allocated the next block."""
        cl = self.cluster
        n = len(cl.servers)
        if n <= len(self.conns):
            return
        add = n - len(self.conns)
        with ClusterClient._port_lock:
            base = ClusterClient._next_base_port
            ClusterClient._next_base_port += add
        epoch = cl.epoch if self._armed else -1
        for i in range(add):
            conn = ShardConnection(cl.servers[len(self.conns)],
                                   self._ip, base + i, self.tenant)
            conn.epoch = epoch
            self.conns.append(conn)
            self._lat_pos.append(0)
        with self._lock:
            while len(self._shard_outstanding) < n:
                self._shard_outstanding.append(0)
            while len(self._dirty_flag) < n:
                self._dirty_flag.append(False)

    # -- routing: the cluster's ring ----------------------------------------------
    def _locate(self, gfid: int) -> tuple[int, int]:
        loc = self.cluster.locate(gfid)
        return loc.shard, loc.local_fid

    def _route_of(self, shard: int) -> int:
        return self.cluster.route_of(shard)

    def _shard_for_key(self, key) -> int:
        return self.cluster.shard_for_key(key)

    def _step(self) -> int:
        """Step the cluster; on replicated or elastic clusters also
        reconcile failovers (an epoch bump re-routes and replays everything
        parked on a dead shard)."""
        work = self.cluster.pump()
        if self._armed:
            work += self._sync_epoch()
        return work

    def _on_redirect(self, hint: bytes) -> None:
        self._sync_epoch()

    def _sync_epoch(self) -> int:
        """Adopt the cluster's ring epoch after a failover or flip.

        Updates every connection's outgoing epoch tag and re-routes each
        unanswered request parked on a now-dead shard — the dead shard can
        never answer, so without this those rids would hang forever.
        Returns the number of requests moved (work, for pump loops)."""
        cur = self.cluster.epoch
        if cur == self._epoch_seen:
            return 0
        self._epoch_seen = cur
        self._grow_conns()   # an elastic add_shard bumps the epoch too
        for conn in self.conns:
            conn.epoch = cur
        dead = self._dead
        if not dead:
            return 0
        moved = 0
        responses = self.responses
        for rid, shard in list(self._rid_shard.items()):
            if shard not in dead or rid in responses:
                continue
            if self._resubmit(rid):
                moved += 1
        return moved
