"""Cross-language golden checks: the C++ common layer must be bit-compatible
with fastdfs_tpu/common (file IDs minted by the C++ storage daemon must
decode in the Python client and vice versa)."""

import hashlib
import os
import random
import subprocess
import zlib

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILD = os.path.join(REPO, "native", "build")
CODEC = os.path.join(BUILD, "fdfs_codec")
COMMON_TEST = os.path.join(BUILD, "common_test")
TRACKER_TEST = os.path.join(BUILD, "tracker_test")
STORAGE_TEST = os.path.join(BUILD, "storage_test")


def _ensure_built():
    # TRACKER_TEST doubles as the staleness sentinel: a build tree from
    # before the stats subsystem has codec+common_test but not it, and
    # must be rebuilt.
    from tests.harness import ensure_native_built
    ensure_native_built((CODEC, COMMON_TEST, TRACKER_TEST, STORAGE_TEST))


@pytest.fixture(scope="module", autouse=True)
def built():
    _ensure_built()


def _run(*args, stdin: bytes = b"") -> str:
    out = subprocess.run([CODEC, *args], input=stdin, capture_output=True,
                         check=True)
    return out.stdout.decode().strip()


def test_cpp_unit_tests_pass():
    subprocess.run([COMMON_TEST], check=True, capture_output=True)


def test_cpp_tracker_tests_pass():
    # Built by the same configure pass; covers the beat-stats ->
    # ClusterStatJson round-trip under the generated field names.
    subprocess.run([TRACKER_TEST], check=True, capture_output=True)


def test_cpp_storage_tests_pass():
    # The stores under the daemon (trunk, chunk store, slabs, EC): among
    # them that one slab compaction ends under a foreground that kills
    # records as fast as it copies them.
    subprocess.run([STORAGE_TEST], check=True, capture_output=True)


def test_generated_protocol_header_current():
    import sys
    sys.path.insert(0, os.path.join(REPO, "native"))
    import gen_protocol
    with open(os.path.join(REPO, "native", "common", "protocol_gen.h")) as fh:
        assert fh.read() == gen_protocol.generate(), (
            "protocol_gen.h is stale; run native/gen_protocol.py")


def test_protocol_manifest_current():
    # The manifest is the machine-readable contract fdfs_lint checks the
    # tree against; a hand-edit (or a protocol.py change without
    # regeneration) must fail loudly here, not drift silently.
    import sys
    sys.path.insert(0, os.path.join(REPO, "native"))
    import gen_protocol
    with open(os.path.join(REPO, "native", "protocol_manifest.json")) as fh:
        assert fh.read() == gen_protocol.manifest_json(
            gen_protocol.build_manifest()), (
            "protocol_manifest.json is stale; run native/gen_protocol.py")


def test_file_id_cpp_encode_python_decode():
    from fastdfs_tpu.common.fileid import decode_file_id
    fid = _run("encode", "group1", "0", "192.168.1.102", "1406000000",
               "30790", "4243582780", "jpg", "42")
    parsed, info = decode_file_id(fid)
    assert parsed.group == "group1"
    assert info.source_ip == "192.168.1.102"
    assert info.create_timestamp == 1406000000
    assert info.file_size == 30790
    assert info.crc32 == 4243582780
    assert info.uniquifier == 42


def test_file_id_python_encode_cpp_decode():
    from fastdfs_tpu.common.fileid import encode_file_id
    fid = encode_file_id("grp", 7, "10.1.2.3", 1700000000, 123456, 999,
                         ext="dat", uniquifier=17)
    out = _run("decode", fid)
    assert "group=grp" in out and "spi=7" in out
    assert "ip=10.1.2.3" in out and "ts=1700000000" in out
    assert "size=123456" in out and "crc=999" in out and "uniq=17" in out


def test_file_id_fuzz_cross():
    from fastdfs_tpu.common.fileid import decode_file_id
    rng = random.Random(77)
    for _ in range(20):
        ip = ".".join(str(rng.randrange(256)) for _ in range(4))
        ts, size = rng.randrange(2**32), rng.randrange(2**48)
        crc, uniq = rng.randrange(2**32), rng.randrange(2**12)
        fid = _run("encode", "g9", "3", ip, str(ts), str(size), str(crc),
                   "bin", str(uniq))
        _, info = decode_file_id(fid)
        assert (info.source_ip, info.create_timestamp, info.file_size,
                info.crc32, info.uniquifier) == (ip, ts, size, crc, uniq)


def test_sha1_matches():
    data = os.urandom(100_000)
    assert _run("sha1", stdin=data) == hashlib.sha1(data).hexdigest()


def test_crc32_matches_zlib():
    data = os.urandom(50_000)
    assert int(_run("crc32", stdin=data)) == zlib.crc32(data)


# One case a length: the ends of every loop `Crc32` has (the byte tail under
# 8, eight bytes a step, 64 bytes a fold with 16-byte folds after it) and
# a length past 1 MiB that is a multiple of none of them.
CRC_LENGTHS = [*range(0, 71), 255, 256, 257, 4095, 4096, 4097, (1 << 20) + 3]
_CRC_BYTES = random.Random(34).randbytes(CRC_LENGTHS[-1])


@pytest.mark.parametrize("length", CRC_LENGTHS)
def test_crc32_matches_zlib_at_length(length):
    data = _CRC_BYTES[:length]
    assert int(_run("crc32", stdin=data)) == zlib.crc32(data)


def test_base64_matches():
    import base64
    raw = os.urandom(20)
    got = _run("b64e", raw.hex())
    want = base64.urlsafe_b64encode(raw).rstrip(b"=").decode()
    assert got == want


def _parse_kv_lines(out: str) -> dict:
    """Parse `key=value` codec output; repeated keys collect into lists."""
    kv: dict = {}
    for line in out.splitlines():
        line = line.strip()
        if not line or "=" not in line:
            continue
        k, _, v = line.partition("=")
        if k in kv:
            if not isinstance(kv[k], list):
                kv[k] = [kv[k]]
            kv[k].append(v)
        else:
            kv[k] = v
    return kv


def test_placement_wire_golden():
    # `fdfs_codec placement-wire` drives the REAL C++ epoch packer
    # (tracker/placement.cc PackWire) over a 3-group fixture with group2
    # draining; the hex must decode under the Python QUERY_PLACEMENT
    # parser and the per-key jump picks must match the Python jump hash.
    from fastdfs_tpu.common.jumphash import jump_hash, placement_key
    from fastdfs_tpu.common.protocol import buff2long, unpack_group_name
    out = _run("placement-wire")
    lines = out.splitlines()
    kv = _parse_kv_lines(out)
    assert kv["version"] == "4"
    body = bytes.fromhex(kv["response"])
    # Wire: 8B version + 8B count + per entry (16B group + 1B state +
    # 8B member count + per member (16B ip + 8B port)).
    assert buff2long(body, 0) == 4
    assert buff2long(body, 8) == 3
    off = 16
    entries = []
    for _ in range(3):
        group = unpack_group_name(body[off:off + 16])
        state = body[off + 16]
        members_n = buff2long(body, off + 17)
        off += 25
        members = []
        for _ in range(members_n):
            members.append((body[off:off + 16].rstrip(b"\x00").decode(),
                            buff2long(body, off + 16)))
            off += 24
        entries.append((group, state, members))
    assert off == len(body)
    assert entries == [
        ("group1", 0, [("10.0.0.1", 23000)]),
        ("group2", 1, [("10.0.0.2", 23001)]),
        ("group3", 0, [("10.0.0.3", 23002), ("10.0.0.4", 23003)]),
    ]
    # jump lines: C++ PlacementKey/JumpHash vs the Python twins, over
    # the 2 ACTIVE groups (group2 is draining).
    checked = 0
    for line in lines:
        if not line.startswith("key="):
            continue
        parts = dict(p.split("=", 1) for p in line.split())
        assert int(parts["placement_key"]) == placement_key(parts["key"])
        assert int(parts["jump"]) == jump_hash(placement_key(parts["key"]), 2)
        checked += 1
    assert checked == 4


def test_group_admin_golden():
    # `fdfs_codec group-admin` pins the GROUP_DRAIN / GROUP_REACTIVATE
    # request body (16B group) and the OK response (8B BE new version)
    # against the Python packers.
    from fastdfs_tpu.common.protocol import long2buff, pack_group_name
    kv = _parse_kv_lines(_run("group-admin"))
    want_req = pack_group_name("group2").hex()
    assert kv["drain_request"] == want_req
    assert kv["reactivate_request"] == want_req
    assert kv["ok_response"] == long2buff(4).hex()
