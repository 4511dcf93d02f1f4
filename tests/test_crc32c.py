"""crc32c: native kernel vs TPU bitmatrix kernel vs known vectors;
the dispatched kernel (three interleaved chains on an sse4.2 host) vs
the table kernel on every length, seed, alignment and chaining;
Checksummer calculate/verify semantics."""
import random

import numpy as np
import pytest

from ceph_tpu.native import ec_native
from ceph_tpu.ops import crc32c as crc_dev
from ceph_tpu.utils.checksummer import Checksummer


def test_known_vector():
    # iSCSI check value: crc32c("123456789") = 0xE3069283 (standard, i.e.
    # seed -1 + final xor; ceph convention omits the final xor)
    assert ec_native.crc32c(b"123456789") ^ 0xFFFFFFFF == 0xE3069283


# -- the kernel's identity ----------------------------------------------------
# The dispatched kernel cuts a buffer into blocks of 3 x 8192 and 3 x 256
# bytes, runs three chains over each and merges them; the lengths sit on
# and beside every one of those edges.

_LENGTHS = [0, 1, 7, 8, 255, 767, 768, 769, 24575, 24576, 24577,
            3 * 8192 - 1, 3 * 8192 + 1, (1 << 20) + 3, 4 << 20]
_SEEDS = [0, 0xFFFFFFFF, random.Random(0xC5C).getrandbits(32)]
_RAW = random.Random(28).randbytes((4 << 20) + 16)


def _aligned() -> np.ndarray:
    """The test bytes in a buffer whose first byte is 8-byte aligned."""
    backing = np.empty(len(_RAW) + 8, dtype=np.uint8)
    skew = -backing.ctypes.data % 8
    buf = backing[skew:skew + len(_RAW)]
    buf[:] = np.frombuffer(_RAW, dtype=np.uint8)
    assert buf.ctypes.data % 8 == 0
    return buf


def _bitwise(data: bytes, crc: int) -> int:
    """The definition: one bit at a time, reflected 0x1EDC6F41."""
    for byte in data:
        crc ^= byte
        for _ in range(8):
            crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
    return crc


def test_the_live_kernel_is_named():
    impl = ec_native.crc32c_impl()
    if ec_native.native.load().ec_native_have_sse42():
        assert impl == "hw3"
    else:
        assert impl == "sw"


@pytest.mark.parametrize("kernel", ["dispatched", "sw"])
def test_known_vectors_and_the_definition(kernel):
    """The file's vectors, RFC 3720's and the bit-at-a-time definition,
    through the dispatched kernel and, with the hardware path out of
    the way, through the table kernel alone."""
    f = ec_native.crc32c if kernel == "dispatched" else ec_native.crc32c_sw
    assert f(b"123456789") ^ 0xFFFFFFFF == 0xE3069283
    # RFC 3720 B.4: 32 bytes of zeros, of ones, ascending
    assert f(bytes(32)) ^ 0xFFFFFFFF == 0x8A9136AA
    assert f(b"\xff" * 32) ^ 0xFFFFFFFF == 0x62A8AB43
    assert f(bytes(range(32))) ^ 0xFFFFFFFF == 0x46DD794E
    for n in (0, 1, 7, 8, 9, 255, 769, 2309):
        for seed in _SEEDS:
            assert f(_RAW[5:5 + n], seed) == _bitwise(_RAW[5:5 + n], seed)


@pytest.mark.parametrize("seed", _SEEDS, ids=["zero", "ones", "random"])
@pytest.mark.parametrize("n", _LENGTHS)
def test_kernel_identity(n, seed):
    """The dispatched kernel gives what the table kernel gives: from
    every start offset 0-7 of an aligned buffer, over the whole length
    and over the length split at every one of the listed lengths and
    chained, `crc32c(b, crc32c(a)) == crc32c(a + b)`."""
    buf = _aligned()
    for off in range(8):
        if off + n > len(buf):
            continue
        view = buf[off:off + n]
        want = ec_native.crc32c_sw(view, seed)
        assert ec_native.crc32c(view, seed) == want, (n, off)
        # bytes and memoryview callers take other ways into the library
        assert ec_native.crc32c(bytes(view), seed) == want
        assert ec_native.crc32c(memoryview(view), seed) == want
    view = buf[3:3 + n] if 3 + n <= len(buf) else buf[:n]
    want = ec_native.crc32c_sw(view, seed)
    for cut in _LENGTHS:
        if cut > n:
            continue
        head = ec_native.crc32c(view[:cut], seed)
        assert head == ec_native.crc32c_sw(view[:cut], seed)
        assert ec_native.crc32c(view[cut:], head) == want, (n, cut)


@pytest.mark.parametrize("seed", _SEEDS, ids=["zero", "ones", "random"])
@pytest.mark.parametrize("nblocks", [1, 3, 128])
def test_blocks_at_4k_are_a_loop_of_crc32c(nblocks, seed):
    buf = _aligned()[:nblocks * 4096]
    got = ec_native.crc32c_blocks(buf, 4096, seed)
    want = [ec_native.crc32c_sw(buf[i * 4096:(i + 1) * 4096], seed)
            for i in range(nblocks)]
    assert got.tolist() == want
    assert got.tolist() == [
        ec_native.crc32c(buf[i * 4096:(i + 1) * 4096], seed)
        for i in range(nblocks)]


@pytest.mark.parametrize("block_size", [64, 512, 4096])
def test_device_matches_native(block_size):
    rng = np.random.default_rng(9)
    blocks = rng.integers(0, 256, (32, block_size), dtype=np.uint8)
    dev = np.asarray(crc_dev.get_device_crc(block_size)(blocks))
    host = ec_native.crc32c_blocks(blocks, block_size)
    np.testing.assert_array_equal(dev, host)


def test_device_zero_and_seed_const():
    # zero blocks exercise the affine const alone
    blocks = np.zeros((4, 512), dtype=np.uint8)
    dev = np.asarray(crc_dev.get_device_crc(512)(blocks))
    host = ec_native.crc32c_blocks(blocks, 512)
    np.testing.assert_array_equal(dev, host)


def test_checksummer_roundtrip():
    rng = np.random.default_rng(10)
    data = rng.integers(0, 256, 16 * 4096, dtype=np.uint8).tobytes()
    cs = Checksummer("crc32c", 4096)
    sums = cs.calculate(data)
    assert sums.shape == (16,)
    assert cs.verify(data, sums) == -1
    corrupted = bytearray(data)
    corrupted[5 * 4096 + 17] ^= 0xFF
    assert cs.verify(bytes(corrupted), sums) == 5 * 4096


def test_checksummer_device_path_matches_host():
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, 8 * 512, dtype=np.uint8).tobytes()
    host = Checksummer("crc32c", 512, use_device=False).calculate(data)
    dev = Checksummer("crc32c", 512, use_device=True).calculate(data)
    np.testing.assert_array_equal(host, dev)


def test_checksummer_truncated_types():
    data = bytes(range(256)) * 16
    c8 = Checksummer("crc32c_8", 512).calculate(data)
    c32 = Checksummer("crc32c", 512).calculate(data)
    np.testing.assert_array_equal(c8, c32 & 0xFF)
    assert (Checksummer("crc32c_16", 512).calculate(data) <= 0xFFFF).all()


def test_checksummer_rejects_misaligned():
    with pytest.raises(ValueError):
        Checksummer("crc32c", 4096).calculate(b"x" * 100)
    with pytest.raises(ValueError):
        Checksummer("crc32c", 1000)
    with pytest.raises(ValueError):
        Checksummer("md5", 4096)
