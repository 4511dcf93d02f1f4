"""`tpu` plugin — the flagship erasure code, designed for the accelerator.

This is the plugin the north-star benchmark targets (BASELINE.json): the
reference's `ErasureCodeInterface::encode_chunks` contract, but engineered
around the accelerator's cost model: a dispatch pays a launch and a
host<->device round trip whatever its size, so per-stripe calls spend
their time on overhead and only batches keep the kernel busy.

So the plugin exposes, beyond the scalar interface:
  - encode_stripes/decode_stripes: (batch, k, S) one-dispatch batch APIs —
    the ECUtil::encode stripe loop (reference src/osd/ECUtil.cc:134) maps
    here, amortizing transfer and launch across concurrent RMW pipelines;
  - pipelined host-buffer encode with split batches so H2D of batch i+1
    overlaps compute of batch i (double buffering);
  - device-resident mode for callers that keep chunks in HBM (the OSD
    bridge and the benchmark steady state).

Techniques: reed_sol_van (default), cauchy_good. Matrices follow the
published jerasure constructions (Plank-Ding 2005 extended-Vandermonde
systematization; Plank-Xu 2006 cauchy_good) over the same field (0x11D),
validated in-repo against an independent from-scratch re-derivation
(tests/test_gf256_independent.py: peasant-multiply arithmetic, Fermat
inversion, full 256x256 table cross-check). A live jerasure build is not
available here, so interop with real jerasure-encoded chunks is
construction-level compatible, not verified against jerasure binaries.
"""
from __future__ import annotations

import time
from typing import Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ec import gf256
from ceph_tpu.ec.interface import ErasureCodeError
from ceph_tpu.ec.plugin_jerasure import ErasureCodeJerasure
from ceph_tpu.ec.registry import (ERASURE_CODE_VERSION, ErasureCodePlugin,
                                  ErasureCodePluginRegistry)
from ceph_tpu.ops import rs_codec
from ceph_tpu.utils import copytrack, tracer

__erasure_code_version__ = ERASURE_CODE_VERSION

DEFAULT_K = 8
DEFAULT_M = 3


def _device_of(arr) -> str:
    """`platform:id` of a committed single-device array ("sharded" for
    mesh-placed inputs) — the span label the per-device utilization
    dashboards join against."""
    try:
        ds = arr.devices()
        if len(ds) != 1:
            return "sharded"
        d = next(iter(ds))
        return f"{d.platform}:{d.id}"
    except Exception:
        return "unknown"


def _profiled_roundtrip(kernel, host_batch, timings: list) -> np.ndarray:
    """One serialized H2D -> kernel -> D2H round trip, accumulating the
    three stage durations into `timings` ([h2d_s, kernel_s, d2h_s]).
    Attribution-mode only (tracer.set_profile_dispatch): the explicit
    block_until_ready per stage forfeits the transfer/compute overlap
    to make the splits real."""
    t0 = time.perf_counter()
    dev = jax.block_until_ready(jnp.asarray(host_batch))
    t1 = time.perf_counter()
    res = jax.block_until_ready(kernel(dev))
    t2 = time.perf_counter()
    out = np.asarray(res)
    t3 = time.perf_counter()
    timings[0] += t1 - t0
    timings[1] += t2 - t1
    timings[2] += t3 - t2
    return out


def _record_roundtrip(timings: list, in_bytes: int, out_bytes: int,
                      sp) -> None:
    """Feed accumulated round-trip timings to the copy ledger and the
    dispatch span (the attribution waterfall's h2d/kernel/d2h buckets)."""
    h2d_s, kernel_s, d2h_s = timings
    copytrack.copied("h2d", in_bytes, h2d_s)
    copytrack.copied("d2h", out_bytes, d2h_s)
    sp.set_tag("h2d_us", round(h2d_s * 1e6, 1))
    sp.set_tag("kernel_us", round(kernel_s * 1e6, 1))
    sp.set_tag("d2h_us", round(d2h_s * 1e6, 1))


class ErasureCodeTpu(ErasureCodeJerasure):
    technique = "reed_sol_van"
    #: batched APIs dispatch to the accelerator: the offload service
    #: routes/queues only plugins that set this — the jerasure family
    #: has the same encode_stripes signature but runs on host, where
    #: the admission queue's linger buys nothing
    device_batched = True

    def init(self, profile: Mapping[str, str]) -> None:
        profile = dict(profile)
        profile.setdefault("k", str(DEFAULT_K))
        profile.setdefault("m", str(DEFAULT_M))
        super().init(profile)
        # pipeline depth for host-buffer batches (number of sub-batches whose
        # transfers overlap compute); 1 disables double buffering
        self.pipeline_depth = self.to_int("pipeline-depth", profile, 4, minimum=1)

    def _build_matrix(self) -> np.ndarray:
        if self._profile.get("technique", "reed_sol_van") == "cauchy_good":
            return gf256.cauchy_good_matrix(self.k, self.m)
        return gf256.reed_sol_van_matrix(self.k, self.m)

    def _check_technique(self) -> None:
        tech = self._profile.get("technique", "reed_sol_van")
        if tech not in ("reed_sol_van", "cauchy_good"):
            raise ErasureCodeError(f"tpu technique {tech!r} unsupported")

    # -- batched data path ---------------------------------------------------

    def encode_stripes(self, data: np.ndarray | jax.Array) -> np.ndarray | jax.Array:
        """(batch, k, S) -> (batch, m, S) parity. numpy in => pipelined
        host transfer + numpy out; device array in => device array out.
        Each call is one traced device dispatch: the span separates
        device-resident time from host-buffer (H2D + compute + D2H)
        time, per stripe batch."""
        device_resident = isinstance(data, jax.Array)
        with tracer.span("tpu_encode_dispatch") as sp:
            if sp is not None:
                sp.set_tag("mode", "device" if device_resident
                           else "host-pipelined")
                sp.set_tag("batch", int(data.shape[0]))
                sp.set_tag("bytes", int(data.size))
                sp.set_tag("k", self.k)
                sp.set_tag("m", self.m)
                if device_resident:
                    # which mesh slot this batch landed on (the offload
                    # service's device-affine routing made the choice)
                    sp.set_tag("device", _device_of(data))
            if device_resident:
                return self._encoder.apply_batch_device(data)
            return self._encode_host_pipelined(
                np.ascontiguousarray(data, dtype=np.uint8), sp=sp)

    def _encode_host_pipelined(self, data: np.ndarray,
                               sp=None) -> np.ndarray:
        b = data.shape[0]
        depth = min(self.pipeline_depth, b)
        splits = np.array_split(np.arange(b), depth)
        if sp is not None and tracer.profile_dispatch():
            # attribution mode (tracer.set_profile_dispatch): serialize
            # each pipeline stage so the span carries REAL h2d/kernel/
            # d2h splits — costs the transfer/compute overlap, so it
            # never rides plain tracer_enabled
            return self._encode_host_profiled(data, splits, sp)
        # enqueue all transfers+dispatches first (async), then collect —
        # XLA/PJRT overlaps H2D of later sub-batches with earlier compute
        outs = []
        for idx in splits:
            if len(idx) == 0:
                continue
            dev = jnp.asarray(data[idx[0]: idx[-1] + 1])
            outs.append(self._encoder.apply_batch_device(dev))
        out = np.concatenate([np.asarray(o) for o in outs], axis=0)
        copytrack.copied("h2d", int(data.nbytes))
        copytrack.copied("d2h", int(out.nbytes))
        return out

    def _encode_host_profiled(self, data: np.ndarray, splits,
                              sp) -> np.ndarray:
        outs = []
        timings = [0.0, 0.0, 0.0]
        for idx in splits:
            if len(idx) == 0:
                continue
            outs.append(_profiled_roundtrip(
                self._encoder.apply_batch_device,
                data[idx[0]: idx[-1] + 1], timings))
        out = np.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
        _record_roundtrip(timings, int(data.nbytes), int(out.nbytes), sp)
        return out

    def decode_stripes(self, avail_ids: tuple[int, ...], want_ids: tuple[int, ...],
                       chunks: np.ndarray | jax.Array) -> np.ndarray | jax.Array:
        """Batched reconstruction: `chunks` is (batch, k, S) holding the
        available chunks stacked in `avail_ids` order; returns the
        reconstructed `want_ids` chunks as (batch, len(want), S)."""
        R = rs_codec.recovery_matrix(self.coding_matrix, avail_ids, want_ids)
        codec = rs_codec.MatrixCodec.get(R)
        device_resident = isinstance(chunks, jax.Array)
        with tracer.span("tpu_decode_dispatch") as sp:
            if sp is not None:
                sp.set_tag("mode", "device" if device_resident else "host")
                sp.set_tag("batch", int(chunks.shape[0]))
                sp.set_tag("bytes", int(chunks.size))
                sp.set_tag("want", list(want_ids))
                if device_resident:
                    sp.set_tag("device", _device_of(chunks))
            if device_resident:
                return codec.apply_batch_device(chunks)
            chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
            if sp is not None and tracer.profile_dispatch():
                timings = [0.0, 0.0, 0.0]
                out = _profiled_roundtrip(codec.apply_batch_device,
                                          chunks, timings)
                _record_roundtrip(timings, int(chunks.nbytes),
                                  int(out.nbytes), sp)
                return out
            dev = jnp.asarray(chunks)
            out = np.asarray(codec.apply_batch_device(dev))
            copytrack.copied("h2d", int(chunks.nbytes))
            copytrack.copied("d2h", int(out.nbytes))
            return out


class ErasureCodePluginTpu(ErasureCodePlugin):
    def factory(self, profile: Mapping[str, str], directory: str | None = None):
        instance = ErasureCodeTpu()
        instance.init(profile)
        return instance


def __erasure_code_init__(name: str, directory: str | None = None):
    ErasureCodePluginRegistry.instance().add(name, ErasureCodePluginTpu())
