"""Process-wide TPU offload service — dynamic batching for the in-situ
EC data path (see service.py for the full design notes)."""
from ceph_tpu.offload.service import (OFFLOAD_OPTIONS, OffloadService,
                                      get_service, get_service_or_none,
                                      register_config)

__all__ = ["OFFLOAD_OPTIONS", "OffloadService", "get_service",
           "get_service_or_none", "register_config"]
