"""Zero-copy data plane (ISSUE 24): the same-host mmap shuffle fast
path (locate handshake, lazy per-frame CRC verify, socket fallback +
quarantine/repair on a corrupt mapped segment, moved-only booking) and
dictionary-encoded string serde (roundtrips, null/empty strings,
cardinality-overflow fallback to plain encoding).

The A/B latency/byte gates live in tools/zerocopy_bench.py
(`make check-zerocopy`); the armed end-to-end corruption cell is in
tools/chaos_soak.py --durability."""

import os
import struct
import zlib

import numpy as np
import pytest

from blaze_tpu.config import conf
from blaze_tpu.runtime import artifacts, faults
from blaze_tpu.runtime import shuffle_server as ss


@pytest.fixture(autouse=True)
def _checksums_on():
    saved = (conf.artifact_checksums, conf.monitor_enabled,
             conf.shuffle_mmap_enabled, conf.dict_encode_strings)
    conf.artifact_checksums = True
    conf.monitor_enabled = True
    yield
    (conf.artifact_checksums, conf.monitor_enabled,
     conf.shuffle_mmap_enabled, conf.dict_encode_strings) = saved
    faults.install(None)


def _frame(payload: bytes) -> bytes:
    return b"BTB1" + struct.pack("<II", len(payload), len(payload)) + payload


def _commit_pair(tmp_path, payloads, name="shuffle_0_0"):
    data = str(tmp_path / f"{name}.data")
    index = str(tmp_path / f"{name}.index")
    frames = [_frame(p) for p in payloads]
    offsets = [0]
    for fr in frames:
        offsets.append(offsets[-1] + len(fr))

    def write(tmp_data, tmp_index):
        with open(tmp_data, "wb") as f:
            f.write(b"".join(frames))
        with open(tmp_index, "wb") as f:
            f.write(struct.pack(f"<{len(offsets)}Q", *offsets))
        return tuple(len(fr) for fr in frames)

    artifacts.commit_shuffle_pair(write, data, index)
    return data, index, frames


@pytest.fixture()
def served_pair(tmp_path):
    """A live server+client over one committed 3-partition pair."""
    data, index, frames = _commit_pair(
        tmp_path, [b"alpha" * 40, b"beta" * 30, b"gamma" * 20])
    server = ss.ShuffleServer(str(tmp_path / "zc.sock"))
    server.register_shuffle("q/shuffle:0", [(data, index)])
    server.start()
    client = ss.ShuffleClient(server.sock_path)
    yield data, index, frames, server, client
    client.close()
    server.close()


class TestMmapFastPath:
    def test_hit_returns_memoryviews_books_moved_only(self, served_pair):
        from blaze_tpu.runtime import monitor

        data, index, frames, server, client = served_pair
        conf.shuffle_mmap_enabled = True
        copied0, moved0 = monitor.copy_totals()
        zc0 = monitor.zerocopy_stats()
        for p, fr in enumerate(frames):
            got = client.fetch_frames("q/shuffle:0", p)
            assert all(isinstance(g, memoryview) for g in got)
            assert b"".join(bytes(g) for g in got) == fr
        copied1, moved1 = monitor.copy_totals()
        zc1 = monitor.zerocopy_stats()
        # single-entry booking: a mmap hit is a move, never a copy
        assert copied1["shuffle"] - copied0["shuffle"] == 0
        assert (moved1["shuffle"] - moved0["shuffle"]
                == sum(len(fr) for fr in frames))
        assert zc1["shuffle_mmap_hits"] - zc0["shuffle_mmap_hits"] == 3
        assert (zc1["shuffle_mmap_fallbacks"]
                - zc0["shuffle_mmap_fallbacks"]) == 0

    def test_knob_off_uses_socket_and_books_copy(self, served_pair):
        from blaze_tpu.runtime import monitor

        data, index, frames, server, client = served_pair
        conf.shuffle_mmap_enabled = False
        copied0, _ = monitor.copy_totals()
        zc0 = monitor.zerocopy_stats()
        got = client.fetch_frames("q/shuffle:0", 1)
        assert b"".join(bytes(g) for g in got) == frames[1]
        copied1, _ = monitor.copy_totals()
        zc1 = monitor.zerocopy_stats()
        assert copied1["shuffle"] - copied0["shuffle"] == len(frames[1])
        assert zc1["shuffle_mmap_hits"] - zc0["shuffle_mmap_hits"] == 0

    def test_broadcast_rid_misses_without_fallback_count(self, tmp_path):
        from blaze_tpu.runtime import monitor

        server = ss.ShuffleServer(str(tmp_path / "bc.sock"))
        server.register_frames("q/broadcast:1", [_frame(b"bc" * 10)])
        server.start()
        client = ss.ShuffleClient(server.sock_path)
        try:
            conf.shuffle_mmap_enabled = True
            zc0 = monitor.zerocopy_stats()
            got = client.fetch_frames("q/broadcast:1", 0)
            assert b"".join(bytes(g) for g in got) == _frame(b"bc" * 10)
            zc1 = monitor.zerocopy_stats()
            # in-memory frame list: not file-backed, a miss — but not a
            # fallback (nothing was mapped and then abandoned)
            assert (zc1["shuffle_mmap_fallbacks"]
                    - zc0["shuffle_mmap_fallbacks"]) == 0
            assert zc1["shuffle_mmap_hits"] - zc0["shuffle_mmap_hits"] == 0
        finally:
            client.close()
            server.close()

    def test_corrupt_mapped_segment_lazy_crc_falls_back_and_repairs(
            self, tmp_path):
        """The mmap-path integrity chain end to end: bit-flip a mapped
        partition, lazy CRC detects on first touch, the fetch falls back
        to the socket (which quarantines + lineage-repairs server-side),
        and the NEXT fetch maps the repaired pair again."""
        from blaze_tpu.runtime import monitor

        payloads = [b"p0" * 30, b"p1" * 30, b"p2" * 30]
        data, index, frames = _commit_pair(tmp_path, payloads)

        def repair():
            return _commit_pair(tmp_path, payloads, name="repaired")[:2]

        artifacts.register_repair(data, repair)
        server = ss.ShuffleServer(str(tmp_path / "cr.sock"))
        server.register_shuffle("q/shuffle:0", [(data, index)])
        server.start()
        client = ss.ShuffleClient(server.sock_path)
        try:
            conf.shuffle_mmap_enabled = True
            # corrupt partition 1's body ON DISK after commit: the map
            # sees the flipped byte, the footer CRC does not match
            offsets, meta = artifacts.read_index(index)
            off1 = struct.unpack("<Q", offsets[8:16])[0]
            with open(data, "r+b") as f:
                f.seek(off1 + 13)
                b = f.read(1)
                f.seek(off1 + 13)
                f.write(bytes([b[0] ^ 0x40]))

            before = artifacts.corruption_stats()
            zc0 = monitor.zerocopy_stats()
            got = client.fetch_frames("q/shuffle:0", 1)
            # the answer is still RIGHT (socket path served the repaired
            # lineage) — zero wrong answers is the whole point
            assert b"".join(bytes(g) for g in got) == frames[1]
            zc1 = monitor.zerocopy_stats()
            after = artifacts.corruption_stats()
            assert (zc1["shuffle_mmap_fallbacks"]
                    - zc0["shuffle_mmap_fallbacks"]) == 1
            assert after["corruptions"] - before["corruptions"] >= 1
            assert after["quarantined"] - before["quarantined"] >= 1
            assert after["repaired"] - before["repaired"] >= 1

            # next fetch re-locates: the redirect now points at the
            # repaired pair, which maps and verifies clean
            got2 = client.fetch_frames("q/shuffle:0", 2)
            assert b"".join(bytes(g) for g in got2) == frames[2]
            assert all(isinstance(g, memoryview) for g in got2)
            zc2 = monitor.zerocopy_stats()
            assert zc2["shuffle_mmap_hits"] - zc1["shuffle_mmap_hits"] == 1
        finally:
            client.close()
            server.close()

    def test_locate_protocol_resolves_outputs(self, served_pair):
        data, index, frames, server, client = served_pair
        with client._lock:
            outs = client._locate_locked("q/shuffle:0")
        assert [list(o) for o in outs] == [[data, index]]
        with client._lock:
            assert client._locate_locked("q/no-such-rid") is None


def _batch(vals, schema=None):
    from blaze_tpu.columnar import INT64, STRING, ColumnBatch, Field, Schema

    schema = schema or Schema([Field("k", INT64), Field("s", STRING)])
    return schema, ColumnBatch.from_numpy(
        {"k": np.arange(len(vals), dtype=np.int64), "s": list(vals)},
        schema)


def _roundtrip_host(schema, batch):
    from blaze_tpu.columnar import serde

    blob = serde.serialize_batch(batch)
    hb = serde.deserialize_batch_host(blob, schema)
    from blaze_tpu.ops.host_sort import host_to_pylike

    return blob, host_to_pylike(hb)


class TestDictEncoding:
    def test_dict_roundtrip_host_and_device(self):
        from blaze_tpu.columnar import serde

        vals = ["tokyo", "osaka", "tokyo", "", "kyoto", "osaka"] * 50
        schema, batch = _batch(vals)
        conf.dict_encode_strings = True
        blob, pyl = _roundtrip_host(schema, batch)
        assert [v.decode() for v in pyl["s"]] == vals
        dev = serde.deserialize_batch(blob, schema)
        got = dev.to_numpy()["s"]
        assert [v.decode() if isinstance(v, bytes) else v
                for v in got] == vals

    def test_dict_counter_and_smaller_frames(self):
        from blaze_tpu.columnar import serde
        from blaze_tpu.runtime import monitor

        vals = ["alpha_city", "beta_city"] * 400
        schema, batch = _batch(vals)
        conf.dict_encode_strings = False
        plain = serde.serialize_batch(batch)
        conf.dict_encode_strings = True
        zc0 = monitor.zerocopy_stats()
        enc = serde.serialize_batch(batch)
        zc1 = monitor.zerocopy_stats()
        # the RAW payload length in the frame header (BTB1 | u32 raw_len |
        # u32 comp_len) is what dictionary encoding controls; the
        # compressed length depends on the codec
        raw_enc, _ = struct.unpack("<II", enc[4:12])
        raw_plain, _ = struct.unpack("<II", plain[4:12])
        assert raw_enc < raw_plain
        assert zc1["dict_cols_encoded"] - zc0["dict_cols_encoded"] == 1

    def test_null_and_empty_strings(self):
        from blaze_tpu.columnar import INT64, STRING, ColumnBatch, Field, Schema
        from blaze_tpu.columnar import serde
        from blaze_tpu.ops.host_sort import host_to_pylike

        schema = Schema([Field("s", STRING)])
        vals = ["", "x", "", "y", ""]
        validity = np.array([True, True, False, True, True])
        batch = ColumnBatch.from_numpy({"s": vals}, schema,
                                       validity={"s": validity})
        for dict_on in (False, True):
            conf.dict_encode_strings = dict_on
            blob = serde.serialize_batch(batch)
            hb = serde.deserialize_batch_host(blob, schema)
            pyl = host_to_pylike(hb)
            got = [None if v is None else v.decode() for v in pyl["s"]]
            assert got == ["", "x", None, "y", ""], f"dict={dict_on}"

    def test_cardinality_overflow_falls_back_to_plain(self):
        from blaze_tpu.columnar import serde

        saved = conf.dict_max_cardinality
        try:
            conf.dict_max_cardinality = 8
            conf.dict_encode_strings = True
            vals = [f"v{i}" for i in range(64)]  # 64 distinct > 8 cap
            schema, batch = _batch(vals)
            blob, pyl = _roundtrip_host(schema, batch)
            assert [v.decode() for v in pyl["s"]] == vals
            # the encoded colblock must be PLAIN (no dict sentinel):
            # decode with a tiny cap would fail otherwise, and the
            # wire stays readable by dict-unaware peers
            hb = serde.deserialize_batch_host(blob, schema)
            assert hb.cols[1].kind == "str"
        finally:
            conf.dict_max_cardinality = saved

    def test_dict_kept_encoded_through_host_decode(self):
        from blaze_tpu.columnar import serde

        vals = ["aa", "bb", "aa", "bb"] * 100
        schema, batch = _batch(vals)
        conf.dict_encode_strings = True
        blob = serde.serialize_batch(batch)
        hb = serde.deserialize_batch_host(blob, schema)
        # ops downstream see i32 codes + the dictionary, not n widened
        # rows: the decode edge is the result merge, not here
        col = hb.cols[1]
        assert col.kind == "dict"
        assert col.codes.dtype == np.int32
        assert len(col.codes) == len(vals)
