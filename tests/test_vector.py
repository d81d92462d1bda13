"""Vectorized data plane: kernel ≡ scalar properties + checksummed writev.

Three layers of coverage for the array-at-a-time kernels (README
"Vectorized data plane"):

  * property tests proving each vector kernel bit-identical to its scalar
    reference (splitmix64, key hashing, frame detect/pack, checksums) —
    the equivalence arguments the burst fast paths rest on;
  * the integrity checksum pipeline end to end: position-salted checksums
    detect bit flips / transpositions / truncation, the block device's
    opt-in per-block checksums fail corrupted reads with EIO on every
    read path (callback, burst, cookie), the torn-writev prefix commits
    its checksums, and a corrupted journal record refuses to replay;
  * the predicate->engine single-probe memo: consumed when the table is
    untouched between the routing probe and the engine step, invalidated
    (and re-probed) by ANY table mutation in between.
"""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.apps.kv_store import (KVClient, KVLocation, ShardedKVStore,
                                 encode_get)
from repro.core import vector, wire
from repro.core.cache_table import CacheTable
from repro.core.dds_server import ServerConfig
from repro.core.file_service import _JREC, SegmentFS
from repro.storage.blockdev import (CRC_BLOCK, STATUS_EINVAL, STATUS_EIO,
                                    STATUS_OK, BlockDevice)

# ---------------------------------------------------------------------------
# Kernel ≡ scalar reference (the equivalence the fast paths rest on)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, vector.MASK64), min_size=1, max_size=64),
       st.sampled_from([0, vector.LEN_SEED, vector.GOLD]))
def test_mix64_matches_scalar_mix(xs, seed):
    arr = np.array(xs, dtype=np.uint64)
    got = vector.mix64(arr, seed)
    want = [vector.scalar_mix(x, seed) for x in xs]
    assert got.tolist() == want


@settings(max_examples=20, deadline=None)
@given(st.lists(st.binary(min_size=0, max_size=24), min_size=1, max_size=32),
       st.lists(st.integers(0, (1 << 62)), min_size=1, max_size=32))
def test_hash_keys_matches_cache_table_hash(bkeys, ikeys):
    t = CacheTable(64)
    keys = list(bkeys) + list(ikeys)
    got = vector.hash_keys(keys)
    want = [t._hash_key(k) for k in keys]
    assert got.tolist() == want


def test_hash_keys_big_int_fallback():
    # > int64: np.fromiter overflows -> the per-item masked path
    t = CacheTable(64)
    keys = [2**64 - 1, 2**63 + 17, 5]
    assert vector.hash_keys(keys).tolist() == [t._hash_key(k) for k in keys]


def _frames(lens):
    out = bytearray()
    for i, ln in enumerate(lens):
        out += ln.to_bytes(4, "little") + bytes([i & 0xFF]) * ln
    return bytes(out)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 40), min_size=1, max_size=24), st.booleans())
def test_uniform_stride_claims_match_greedy_decode(lens, uniform):
    if uniform:
        lens = [max(lens[0], 1)] * len(lens)
    buf = _frames(lens)
    got = vector.uniform_stride(buf, 4)
    if got is None:
        return  # no claim: callers run the scalar walk
    n, stride, ln = got
    # The claim must agree with the greedy sequential decoder: the first
    # n frames all have payload length ln at stride multiples.
    pos = 0
    for _ in range(n):
        assert int.from_bytes(buf[pos:pos + 4], "little") == ln
        pos += 4 + ln
    assert pos == n * stride <= len(buf)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.binary(min_size=0, max_size=32), min_size=1, max_size=24),
       st.booleans())
def test_pack_frames_matches_scalar_join(msgs, uniform):
    if uniform:  # force the n>=8 fixed-stride fast path
        m = msgs[0] or b"x"
        msgs = [m] * max(len(msgs), 8)
    want = b"".join(len(m).to_bytes(4, "little") + m for m in msgs)
    assert bytes(vector.pack_frames(msgs)) == want


@settings(max_examples=40, deadline=None)
@given(st.binary(min_size=0, max_size=200))
def test_checksum64_matches_scalar(blob):
    assert vector.checksum64(blob) == vector.checksum64_scalar(blob)


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_block_checksums_match_per_block(data):
    block = data.draw(st.sampled_from([64, 512, 4096]))
    nblocks = data.draw(st.integers(1, 8))
    mem = np.frombuffer(
        bytes(data.draw(st.integers(0, 255)) for _ in range(64)) * (
            block * nblocks // 64),
        dtype=np.uint8).copy()
    got = vector.block_checksums(mem, 0, nblocks, block)
    want = [vector.checksum64(mem[i * block:(i + 1) * block].tobytes())
            for i in range(nblocks)]
    assert got.tolist() == want


# ---------------------------------------------------------------------------
# Checksum detection properties (the CRC32C role on the writev path)
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(st.binary(min_size=1, max_size=128), st.data())
def test_checksum_detects_bit_flip(blob, data):
    blob = bytearray(blob)
    c0 = vector.checksum64(blob)
    blob[data.draw(st.integers(0, len(blob) - 1))] ^= \
        1 << data.draw(st.integers(0, 7))
    assert vector.checksum64(blob) != c0


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, vector.MASK64), min_size=2, max_size=16),
       st.data())
def test_checksum_detects_word_transposition(words, data):
    i = data.draw(st.integers(0, len(words) - 2))
    j = data.draw(st.integers(i + 1, len(words) - 1))
    if words[i] == words[j]:
        words[j] ^= 1
    blob = b"".join(w.to_bytes(8, "little") for w in words)
    swapped = list(words)
    swapped[i], swapped[j] = swapped[j], swapped[i]
    blob2 = b"".join(w.to_bytes(8, "little") for w in swapped)
    assert vector.checksum64(blob) != vector.checksum64(blob2)


@settings(max_examples=20, deadline=None)
@given(st.binary(min_size=0, max_size=64))
def test_checksum_detects_truncation_and_zero_extension(blob):
    c = vector.checksum64(blob)
    assert vector.checksum64(blob + b"\x00") != c
    if blob:
        assert vector.checksum64(blob[:-1]) != c


# ---------------------------------------------------------------------------
# Device-level block checksums: every read path detects corrupt media
# ---------------------------------------------------------------------------


def test_checksummed_device_fails_corrupted_reads_on_every_path():
    dev = BlockDevice(1 << 20, block_size=512)
    dev.enable_checksums()
    blob = bytes(range(256)) * 16          # one CRC_BLOCK
    acks = []
    dev.submit_write(2 * CRC_BLOCK, blob, on_complete=acks.append)
    dev.poll()
    assert acks == [STATUS_OK]
    assert dev.verify_blocks() == 0        # commit refreshed the stored CRC

    dev._mem[2 * CRC_BLOCK + 17] ^= 0x5A   # out-of-band media corruption
    assert dev.verify_blocks(2 * CRC_BLOCK, CRC_BLOCK) == 1

    # Callback path: EIO, and NO bytes delivered into the caller's view.
    sts = []
    dst = memoryview(bytearray(64))
    dev.submit_read(2 * CRC_BLOCK, 64, dst, on_complete=sts.append)
    dev.poll()
    assert sts == [STATUS_EIO] and bytes(dst) == bytes(64)

    # Burst path: the corrupt op fails alone, its clean neighbor succeeds.
    sts2 = []
    d_ok, d_bad = memoryview(bytearray(64)), memoryview(bytearray(64))
    dev.submit_read_many(
        [(0, 64, d_ok, lambda s: sts2.append(("ok", s))),
         (2 * CRC_BLOCK, 64, d_bad, lambda s: sts2.append(("bad", s)))],
        priority=True)
    dev.poll()
    assert sts2 == [("ok", STATUS_OK), ("bad", STATUS_EIO)]

    # Cookie path: the completion queue carries the EIO.
    dev.submit_read(2 * CRC_BLOCK, 64, memoryview(bytearray(64)), cookie=7)
    dev.poll()
    assert dev.reap() == [(7, STATUS_EIO)]
    assert dev.stats.crc_read_failures == 3

    # A fresh write over the corrupt block re-commits: reads are clean again.
    dev.submit_write(2 * CRC_BLOCK, blob, on_complete=acks.append)
    dev.poll()
    sts3 = []
    out = memoryview(bytearray(len(blob)))
    dev.submit_read(2 * CRC_BLOCK, len(blob), out, on_complete=sts3.append)
    dev.poll()
    assert sts3 == [STATUS_OK] and bytes(out) == blob


def test_torn_writev_prefix_commits_its_checksums():
    dev = BlockDevice(1 << 20, block_size=512)
    dev.enable_checksums()
    dev.inject_torn_writev(nth=1, chunks=1)
    dev.submit_writev(CRC_BLOCK, [b"\x11" * CRC_BLOCK, b"\x22" * CRC_BLOCK],
                      cookie=1)
    dev.poll()
    assert dev.crashed
    assert dev.raw_read(CRC_BLOCK, CRC_BLOCK) == b"\x11" * CRC_BLOCK
    # The prefix that DID reach media carries matching checksums: recovery
    # reads of survived bytes must not false-positive as corruption.
    assert dev.verify_blocks() == 0


def test_raw_write_commits_checksums():
    dev = BlockDevice(1 << 20, block_size=512)
    dev.enable_checksums()
    dev.raw_write(0, b"\x77" * 100)        # metadata-style raw commit
    assert dev.verify_blocks() == 0


def test_server_config_knob_enables_device_checksums():
    from repro.core.dds_server import DDSStorageServer
    srv = DDSStorageServer(ServerConfig(device_capacity=1 << 22,
                                        segment_size=1 << 16,
                                        verify_checksums=True))
    assert srv.device._crc is not None
    assert srv.device.verify_blocks() == 0
    srv2 = DDSStorageServer(ServerConfig(device_capacity=1 << 22,
                                         segment_size=1 << 16))
    assert srv2.device._crc is None        # default: off


# ---------------------------------------------------------------------------
# Journal body checksum: a corrupted committed record refuses to replay
# ---------------------------------------------------------------------------


def _crashed_journaled_write(payload):
    dev = BlockDevice(1 << 22, block_size=512)
    fs = SegmentFS(dev, 1 << 16, journal_segments=2)
    fid = fs.create_file("f")
    assert fs.submit_writev(fid, 0, [payload], cookie=1) == wire.E_OK
    # The device queue holds [journal writev, commit flip, in-place writev]:
    # complete the first two, then crash — committed record, no in-place.
    dev.poll(2)
    dev.crash()
    return dev, fs, fid


def test_committed_journal_record_replays_after_crash():
    payload = b"\x33" * 1024
    dev, fs, fid = _crashed_journaled_write(payload)
    fs2 = SegmentFS.mount(dev, 1 << 16, journal_segments=2)
    rec = fs2.recover_journal()
    assert rec == {"records": 1, "bytes": len(payload)}
    assert fs2.journal_crc_failures == 0
    phys = fs2.files[fid].segments[0] * (1 << 16)
    assert dev.raw_read(phys, len(payload)) == payload


def test_corrupted_journal_record_is_detected_not_replayed():
    payload = b"\x33" * 1024
    dev, fs, fid = _crashed_journaled_write(payload)
    # Flip one payload byte of the committed record on the survived media
    # (header: _JREC fields, then nsegs * u32 segment map, then payload).
    corrupt_at = fs._journal_start + _JREC.size + 4 + 100
    dev._mem[corrupt_at] ^= 0xFF
    fs2 = SegmentFS.mount(dev, 1 << 16, journal_segments=2)
    rec = fs2.recover_journal()
    assert rec == {"records": 0, "bytes": 0}   # refused, scan stopped
    assert fs2.journal_crc_failures == 1


# ---------------------------------------------------------------------------
# Burst read submission: scalar semantics preserved entry for entry
# ---------------------------------------------------------------------------


def test_submit_read_many_order_einval_and_contents():
    dev = BlockDevice(1 << 20, block_size=512)
    media = bytes(range(256)) * 32
    dev.raw_write(0, media)
    sts = []
    outs = [memoryview(bytearray(32)) for _ in range(5)]
    reads = [(i * 32, 32, outs[i], lambda s, i=i: sts.append((i, s)))
             for i in range(5)]
    # An out-of-bounds op in the middle: EINVAL fires AT SUBMIT (scalar
    # semantics), the rest land on the queue in list order.
    reads.insert(2, (1 << 20, 32, memoryview(bytearray(32)),
                     lambda s: sts.append(("inv", s))))
    dev.submit_read_many(reads, priority=True)
    assert sts == [("inv", STATUS_EINVAL)]
    dev.poll()
    assert sts[1:] == [(i, STATUS_OK) for i in range(5)]
    for i, out in enumerate(outs):
        assert bytes(out) == media[i * 32:(i + 1) * 32]
    assert dev.stats.reads == 5


# ---------------------------------------------------------------------------
# Predicate -> engine single-probe memo (epoch-guarded handoff)
# ---------------------------------------------------------------------------


def _memo_stack():
    store = ShardedKVStore(num_shards=1,
                           config=ServerConfig(device_capacity=1 << 24,
                                               segment_size=1 << 18))
    cli = KVClient(store)
    keys = [b"memo-key-%04d" % i for i in range(32)]
    handles = cli.submit([("put", k, b"v" * 32) for k in keys])
    cli.harvest(handles)
    srv = store.cluster.servers[0]
    msgs = [encode_get(1000 + i, k) for i, k in enumerate(keys)]
    payload = b"".join(len(m).to_bytes(4, "little") + m for m in msgs)
    assert len(payload) >= 512  # big enough for the columnar route
    return store, srv, keys, payload


def test_probe_memo_consumed_without_table_mutation():
    store, srv, keys, payload = _memo_stack()
    api, table = srv.api, srv.cache_table
    host, dpu = api.off_pred(payload, table)
    assert not host and len(dpu) == len(keys)
    before = table.stats.lookups
    res = api.prepare_read_many(dpu, table)
    # The memo carried the predicate's probe: the engine did NOT re-probe.
    assert table.stats.lookups == before
    idx = store._states[0].index
    for r, k in zip(res, keys):
        assert r is not None and r[0] == idx[k]


def test_probe_memo_invalidated_by_mutation_between_probe_and_engine():
    store, srv, keys, payload = _memo_stack()
    api, table = srv.api, srv.cache_table
    host, dpu = api.off_pred(payload, table)
    assert not host and len(dpu) == len(keys)
    # ANY table mutation between the routing probe and the engine step
    # bumps the epoch: the memo must be ignored and the burst re-probed.
    table.insert(b"__interloper__", KVLocation(0, 0, 0))
    before = table.stats.lookups
    res = api.prepare_read_many(dpu, table)
    assert table.stats.lookups == before + len(keys)   # full re-probe
    idx = store._states[0].index
    for r, k in zip(res, keys):
        assert r is not None and r[0] == idx[k]


def test_probe_memo_invalidated_by_delete():
    store, srv, keys, payload = _memo_stack()
    api, table = srv.api, srv.cache_table
    host, dpu = api.off_pred(payload, table)
    assert len(dpu) == len(keys)
    table.delete(keys[3])   # the memoized location is now gone
    res = api.prepare_read_many(dpu, table)
    assert res[3] is None   # re-probe sees the delete — never a stale loc
    idx = store._states[0].index
    for i, (r, k) in enumerate(zip(res, keys)):
        if i != 3:
            assert r is not None and r[0] == idx[k]
