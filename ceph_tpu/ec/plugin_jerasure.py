"""`jerasure` plugin: matrix Reed-Solomon techniques on the TPU codec.

Re-creation of the reference's default plugin
(src/erasure-code/jerasure/ErasureCodeJerasure.{h,cc}): techniques are
dispatched by the profile's `technique` key
(ErasureCodePluginJerasure.cc:34-71); each class's prepare() builds its
coding matrix once at init (ErasureCodeJerasure.cc:203). Instead of
jerasure's GF tables + SIMD loops, all techniques lower to the shared
bitplane-matmul codec (ceph_tpu.ops.rs_codec), so the same code runs the
w=8 field math on CPU or TPU (construction-compatible with jerasure;
independently cross-validated in tests/test_gf256_independent.py).

Supported techniques: reed_sol_van, reed_sol_r6_op, cauchy_orig,
cauchy_good (GF(2^8) matrix codes on the bitplane-matmul codec), and the
minimal-density bitmatrix RAID-6 family — liberation, blaum_roth,
liber8tion — lowered onto the GF(2) packet-XOR machinery in
ceph_tpu.ec.bitmatrix (constructions verified MDS at prepare()).
"""
from __future__ import annotations

from typing import Iterable, Mapping

import numpy as np

from ceph_tpu.ec import gf256
from ceph_tpu.ec.interface import ErasureCode, ErasureCodeError
from ceph_tpu.ec.registry import (ERASURE_CODE_VERSION, ErasureCodePlugin,
                                  ErasureCodePluginRegistry)
from ceph_tpu.ops import rs_codec

__erasure_code_version__ = ERASURE_CODE_VERSION

DEFAULT_K = 2
DEFAULT_M = 1
DEFAULT_W = 8


class ErasureCodeJerasure(ErasureCode):
    """Base for matrix techniques; subclasses provide _build_matrix()."""

    technique = "reed_sol_van"

    def __init__(self):
        super().__init__()
        self.w = DEFAULT_W
        self.coding_matrix: np.ndarray | None = None

    DEFAULT_TECHNIQUE_W = DEFAULT_W

    def init(self, profile: Mapping[str, str]) -> None:
        super().init(profile)
        self.k = self.to_int("k", profile, DEFAULT_K, minimum=1)
        self.m = self.to_int("m", profile, DEFAULT_M, minimum=1)
        self.w = self.to_int("w", profile, self.DEFAULT_TECHNIQUE_W)
        self._check_w()
        if self.k + self.m > 256:
            raise ErasureCodeError("k+m must be <= 256 in GF(2^8)")
        self._check_technique()
        self.prepare()
        # normalize defaulted keys back into the profile like the reference
        self._profile.update({"k": str(self.k), "m": str(self.m), "w": str(self.w)})

    def _check_w(self) -> None:
        if self.w != 8:
            # The TPU data path is GF(2^8)-native; other word sizes existed in
            # jerasure for CPU table-size tradeoffs that do not apply here.
            raise ErasureCodeError(f"w={self.w} unsupported; only w=8")

    def _check_technique(self) -> None:
        pass

    def prepare(self) -> None:
        self.coding_matrix = np.asarray(self._build_matrix(), dtype=np.uint8)
        self._encoder = rs_codec.MatrixCodec.get(self.coding_matrix)

    def _build_matrix(self) -> np.ndarray:
        raise NotImplementedError

    # -- kernels ------------------------------------------------------------

    def encode_chunks(self, chunks: dict[int, np.ndarray]) -> None:
        data = np.stack([chunks[i] for i in range(self.k)])
        parity = self._encoder.apply(data)
        for i in range(self.m):
            chunks[self.k + i][:] = parity[i]

    def decode_chunks(self, want_to_read: Iterable[int],
                      chunks: dict[int, np.ndarray],
                      available: set[int]) -> None:
        # `available` is required: the kernel contract supplies `chunks` with
        # zero-filled holes for missing ids, so deriving it as set(chunks)
        # would make every chunk look present and silently skip
        # reconstruction (ADVICE r1).
        want = sorted(set(want_to_read) - available)
        if not want:
            return
        avail = tuple(sorted(available))[: self.k]
        if len(avail) < self.k:
            raise ErasureCodeError(
                f"cannot decode {want}: only {len(avail)} chunks available")
        R = rs_codec.recovery_matrix(self.coding_matrix, avail, tuple(want))
        src = np.stack([chunks[i] for i in avail])
        rec = rs_codec.MatrixCodec.get(R).apply(src)
        for row, i in enumerate(want):
            chunks[i][:] = rec[row]

    # -- batched stripe API (the ec_util one-dispatch driver) ---------------

    def _apply_flat(self, M: np.ndarray, src) -> np.ndarray:
        """(S, rows_in, C) through M (rows_out, rows_in) -> (S, rows_out, C).

        Host arrays: the stripe axis folds into the byte lanes so the
        whole batch is ONE matrix application — via the native
        split-table SIMD codec when available (the OSD write path feeds
        host bytes; per-stripe dispatch was ~100x slower there), else
        one MatrixCodec dispatch (the reference amortizes the same way
        at its ECUtil::encode batching site, src/osd/ECUtil.cc:134).
        Device arrays stay on device (device in => device out, the
        plugin_tpu contract) — silently pulling a jax batch to host
        would hide a D2H transfer inside a "device" bench.
        Host output is stripe-major as a VIEW over shard-major storage:
        the ec_util consumers re-transpose to shard-major, so their
        ascontiguousarray lands back on this buffer for free."""
        import jax
        if isinstance(src, jax.Array):
            return rs_codec.MatrixCodec.get(M).apply_batch_device(src)
        from ceph_tpu.native import ec_native
        src = np.ascontiguousarray(src, dtype=np.uint8)
        S, kin, C = src.shape
        rows = M.shape[0]
        flat = np.ascontiguousarray(src.transpose(1, 0, 2)).reshape(
            kin, S * C)
        if ec_native.available():
            out = np.empty((rows, S * C), dtype=np.uint8)
            ec_native.encode(M, flat, out)
        else:
            out = rs_codec.MatrixCodec.get(M).apply(flat)
        return out.reshape(rows, S, C).transpose(1, 0, 2)

    def encode_stripes(self, data):
        """(S, k, C) data stripes -> (S, m, C) parity, one dispatch."""
        return self._apply_flat(self.coding_matrix, data)

    def decode_stripes(self, avail_ids: tuple[int, ...],
                       want_ids: tuple[int, ...], chunks) -> np.ndarray:
        """Batched reconstruction of `want_ids` from the first-k available
        chunks stacked in `avail_ids` order: (S, k, C) -> (S, want, C)."""
        R = rs_codec.recovery_matrix(self.coding_matrix, tuple(avail_ids),
                                     tuple(want_ids))
        return self._apply_flat(R, chunks)


class ErasureCodeJerasureReedSolomonVandermonde(ErasureCodeJerasure):
    technique = "reed_sol_van"

    def _build_matrix(self) -> np.ndarray:
        return gf256.reed_sol_van_matrix(self.k, self.m)


class ErasureCodeJerasureReedSolomonRAID6(ErasureCodeJerasure):
    technique = "reed_sol_r6_op"

    def _check_technique(self) -> None:
        if self.m != 2:
            raise ErasureCodeError("reed_sol_r6_op requires m=2")

    def _build_matrix(self) -> np.ndarray:
        return gf256.reed_sol_r6_matrix(self.k)


class ErasureCodeJerasureCauchyOrig(ErasureCodeJerasure):
    technique = "cauchy_orig"

    def _build_matrix(self) -> np.ndarray:
        return gf256.cauchy_orig_matrix(self.k, self.m)


class ErasureCodeJerasureCauchyGood(ErasureCodeJerasure):
    technique = "cauchy_good"

    def _build_matrix(self) -> np.ndarray:
        return gf256.cauchy_good_matrix(self.k, self.m)


class ErasureCodeJerasureBitMatrix(ErasureCodeJerasure):
    """Base for the minimal-density GF(2) bitmatrix RAID-6 family
    (liberation/blaum_roth/liber8tion): m=2, word size w, chunk = w
    contiguous packets. Lowers onto ceph_tpu.ec.bitmatrix rather than
    the GF(2^8) codec (these codes are not GF(2^8) matrices)."""

    # the GF(2^8) batched stripe API does not apply to GF(2) bit codes;
    # ec_util's callable() gate sends these through the per-stripe loop
    encode_stripes = None
    decode_stripes = None

    def _check_w(self) -> None:
        pass            # per-technique constraints in _check_technique

    def _check_technique(self) -> None:
        if self.m != 2:
            raise ErasureCodeError(f"{self.technique} requires m=2")
        if self.k > self.w:
            raise ErasureCodeError(
                f"{self.technique}: k={self.k} > w={self.w}")

    def prepare(self) -> None:
        from ceph_tpu.ec import bitmatrix
        self.code = bitmatrix.RAID6BitCode(
            "blaum_roth" if self.technique == "blaum_roth"
            else "liberation", self.k, self.w)

    def get_alignment(self) -> int:
        # chunks must split into w equal packets; keep packets themselves
        # 64-byte aligned for the XOR path
        return self.w * 64

    def encode_chunks(self, chunks: dict[int, np.ndarray]) -> None:
        self.code.encode(chunks)

    def decode_chunks(self, want_to_read: Iterable[int],
                      chunks: dict[int, np.ndarray],
                      available: set[int]) -> None:
        want = sorted(set(want_to_read) - available)
        if not want:
            return
        self.code.decode(want, chunks, available)


class ErasureCodeJerasureLiberation(ErasureCodeJerasureBitMatrix):
    technique = "liberation"
    DEFAULT_TECHNIQUE_W = 7

    def _check_technique(self) -> None:
        super()._check_technique()
        from ceph_tpu.ec.bitmatrix import _is_prime
        if not _is_prime(self.w):
            raise ErasureCodeError(f"liberation: w={self.w} must be prime")


class ErasureCodeJerasureBlaumRoth(ErasureCodeJerasureBitMatrix):
    technique = "blaum_roth"
    DEFAULT_TECHNIQUE_W = 6

    def _check_technique(self) -> None:
        super()._check_technique()
        from ceph_tpu.ec.bitmatrix import _is_prime
        if not _is_prime(self.w + 1):
            raise ErasureCodeError(
                f"blaum_roth: w+1={self.w + 1} must be prime")


class ErasureCodeJerasureLiber8tion(ErasureCodeJerasureBitMatrix):
    technique = "liber8tion"
    DEFAULT_TECHNIQUE_W = 8

    def _check_technique(self) -> None:
        if self.w != 8:
            raise ErasureCodeError("liber8tion requires w=8")
        super()._check_technique()


_TECHNIQUES = {
    cls.technique: cls
    for cls in (
        ErasureCodeJerasureReedSolomonVandermonde,
        ErasureCodeJerasureReedSolomonRAID6,
        ErasureCodeJerasureCauchyOrig,
        ErasureCodeJerasureCauchyGood,
        ErasureCodeJerasureLiberation,
        ErasureCodeJerasureBlaumRoth,
        ErasureCodeJerasureLiber8tion,
    )
}

_DEFERRED: set[str] = set()


class ErasureCodePluginJerasure(ErasureCodePlugin):
    def factory(self, profile: Mapping[str, str],
                directory: str | None = None):
        technique = profile.get("technique", "reed_sol_van")
        cls = _TECHNIQUES.get(technique)
        if cls is None:
            if technique in _DEFERRED:
                raise ErasureCodeError(
                    f"technique {technique!r} not yet implemented")
            raise ErasureCodeError(f"unknown jerasure technique {technique!r}")
        instance = cls()
        instance.init(profile)
        return instance


def __erasure_code_init__(name: str, directory: str | None = None):
    ErasureCodePluginRegistry.instance().add(name, ErasureCodePluginJerasure())
