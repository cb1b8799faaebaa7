"""tpurpc — a TPU-native RPC framework with the capability set of pwrliang/grpc-rdma.

The reference ("RR-Compound", /root/reference) is a gRPC v1.38 fork that swaps the byte
transport under gRPC's endpoint abstraction from TCP to one-sided-write RDMA ring buffers,
selected at runtime by the ``GRPC_PLATFORM_TYPE`` env var (reference:
``src/core/lib/iomgr/iomgr_internal.cc:36-61``).  tpurpc rebuilds that capability seam
TPU-first:

* the swappable byte-pipe lives behind one :class:`tpurpc.core.endpoint.Endpoint`
  interface (reference: ``src/core/lib/iomgr/endpoint.h``),
* the high-performance paths are credit-managed header/footer-framed ring buffers
  (reference: ``src/core/lib/ibverbs/ring_buffer.{h,cc}``) written by one-sided ops,
  with three wakeup disciplines — busy-poll, event-driven, hybrid (reference engines
  ``ev_epollex_rdma_{bp,event,bpev}_linux.cc``),
* receive rings can live in TPU HBM and surface payloads as zero-copy ``jax.Array``s
  (this repo's north star; the reference always copies ring→slice,
  ``ring_buffer.cc:122-191``),
* the wire format is gRPC-compatible (HTTP/2 + length-prefixed messages) so stock
  grpcio clients interoperate.

Package map (SURVEY.md §7):

=================  ===========================================================
``tpurpc.utils``   config / trace / logging / sync plumbing (ref: gpr, gprpp)
``tpurpc.core``    ring, pair, poller, endpoint, tcp, wire (ref: iomgr, ibverbs)
``tpurpc.rpc``     call/stream layer, server, client (ref: surface/, chttp2)
``tpurpc.tpu``     HBM rings, copy ledger, device serialization (north star)
``tpurpc.jaxshim`` grpcio-jax: jax.Array in/out, tensor services, pjit serving
``tpurpc.models``  flagship serving models (ResNet-50 inference server)
``tpurpc.parallel`` mesh/sharding helpers for multi-chip serving
=================  ===========================================================
"""

from tpurpc.version import __version__
from tpurpc.utils.config import Config, Platform

__all__ = ["__version__", "Config", "Platform"]
