"""ceph_tpu — a TPU-native distributed object-storage framework.

A from-scratch rebuild of Ceph's capability surface (reference:
ssdohammer-sl/ceph @ 2024-08-07) designed TPU-first: the erasure-code and
checksum hot paths run as JAX GF(2) matmul kernels on TPU, the cluster
runtime (messenger, CRUSH placement, Paxos monitors, PG-based OSDs, client
library) is rebuilt idiomatically rather than ported.

Subpackages:
  ec          erasure-code plugin layer (interface, registry, plugins)
  ops         device kernels (RS bitplane matmul, crc32c — XLA dot_general
              int8 MXU kernels under plain jax.jit)
  parallel    device-mesh sharding of the codec pipeline (ICI scale-out)
  crush       placement: CRUSH hierarchy/rules + OSDMap epochs
  msg         wire messaging (TLV frames, crc32c, reconnect)
  mon         monitor: single-Paxos, map distribution, EC profile plane
  osd         OSD data plane (EC stripe driver, PGs, backends)
  rados       client library (Objecter-style placement + resend)
  objectstore local object stores (API, MemStore, file-backed store)
  utils       runtime substrate (buffers, config, perf counters, logging)
  tools       CLIs (ec benchmark, object store tools)
"""

import os
import sys

__version__ = "0.1.0"


def _place_compile_cache() -> None:
    """Decide where XLA's persistent compile cache lives — here, once,
    for every entry point: importing any `ceph_tpu` module runs this
    before that module can compile anything. A directory named by
    JAX_COMPILATION_CACHE_DIR is used as given and no other is set in
    code; otherwise the cache goes to one fixed path inside the
    checkout (the path is part of the cache key, so a directory that
    moves never hits). The choice travels in the environment, so child
    processes inherit it."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read its environment at import, before we got here
        jax.config.update("jax_compilation_cache_dir", path)


_place_compile_cache()
