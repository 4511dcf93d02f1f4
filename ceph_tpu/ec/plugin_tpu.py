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

from typing import Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ec import gf256
from ceph_tpu.ec.interface import ErasureCodeError, decode_batch_tags
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
            with jax.profiler.TraceAnnotation(f"rs_encode_r{self.m}"):
                if device_resident:
                    return self._encoder.apply_batch_device(data)
                return self._encode_host_pipelined(
                    np.ascontiguousarray(data, dtype=np.uint8))

    def _encode_host_pipelined(self, data: np.ndarray) -> np.ndarray:
        b = data.shape[0]
        depth = min(self.pipeline_depth, b)
        splits = np.array_split(np.arange(b), depth)
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

    def decode_stripes(self, avail_ids: tuple[int, ...], want_ids: tuple[int, ...],
                       chunks: np.ndarray | jax.Array) -> np.ndarray | jax.Array:
        """Batched reconstruction: `chunks` is (batch, k, S) holding the
        available chunks stacked in `avail_ids` order; the reconstructed
        `want_ids` chunks come back in that order.

        The recovery matrix is padded with zero rows to m, so a decode
        runs the ENCODE program of its batch shape (the bitmatrix is a
        run-time argument of one program per (batch, r, S)): whatever
        has written stripes of a shape has compiled what reads them
        degraded, for any number of lost chunks up to m, and no erasure
        pattern compiles anything. The price is m - r idle output rows.
        numpy in => numpy (batch, len(want), S) out. Device array in =>
        device array (batch, max(len(want), m), S) out, the rows past
        len(want) zero: slicing them off on the device would be one more
        program a shape, so the caller slices after its D2H."""
        r = len(want_ids)
        R = rs_codec.recovery_matrix(self.coding_matrix, avail_ids, want_ids)
        if r < self.m:
            R = np.concatenate(
                [R, np.zeros((self.m - r, self.k), dtype=np.uint8)])
        built = rs_codec.MatrixCodec.misses
        codec = rs_codec.MatrixCodec.get(R)
        device_resident = isinstance(chunks, jax.Array)
        with tracer.span("tpu_decode_dispatch") as sp:
            if sp is not None:
                sp.set_tag("mode", "device" if device_resident else "host")
                # this pattern's first decode: its codec was built now
                sp.set_tag("matrix_miss",
                           rs_codec.MatrixCodec.misses != built)
                sp.set_tag("batch", int(chunks.shape[0]))
                sp.set_tag("bytes", int(chunks.size))
                sp.tags.update(decode_batch_tags(avail_ids, want_ids))
                if device_resident:
                    sp.set_tag("device", _device_of(chunks))
            # the device trace shows one program for both directions;
            # this names the launch on the host's line, at its true r
            with jax.profiler.TraceAnnotation(f"rs_decode_r{r}"):
                if device_resident:
                    return codec.apply_batch_device(chunks)
                chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
                dev = jnp.asarray(chunks)
                out = np.asarray(codec.apply_batch_device(dev))
            copytrack.copied("h2d", int(chunks.nbytes))
            copytrack.copied("d2h", int(out.nbytes))
            return out[:, :r]


class ErasureCodePluginTpu(ErasureCodePlugin):
    def factory(self, profile: Mapping[str, str], directory: str | None = None):
        instance = ErasureCodeTpu()
        instance.init(profile)
        return instance


def __erasure_code_init__(name: str, directory: str | None = None):
    ErasureCodePluginRegistry.instance().add(name, ErasureCodePluginTpu())
