"""sparkrdma_tpu — a TPU-native shuffle framework.

A ground-up re-design of the capability set of SparkRDMA (the Mellanox
RDMA shuffle plugin for Apache Spark, see ``/root/reference``): a pluggable
shuffle manager whose data plane moves map-output blocks through
registered, zero-copy memory instead of the TCP/Netty stack.

Here the "NIC" is the TPU interconnect (ICI): map outputs are serialized
into HBM-resident arenas and exchanged between chips with XLA collectives
(``jax.lax.all_to_all`` / ``ppermute``) driven by a tile-round scheduler,
while a driver-side control plane (hello/announce/publish/fetch-status)
tracks block locations exactly like the reference's driver-mediated
metadata path (reference: RdmaShuffleManager.scala:38-388).

Layer map (mirrors SURVEY.md §1):

    L1  api       TpuShuffleManager        (shuffle/manager.py)
    L2  data      writer/reader/resolver   (shuffle/)
    L3  control   rpc messages + driver    (rpc/, control/)
    L4  transport node/channel/loopback    (transport/), exchange (parallel/)
    L5  device    arenas, pallas kernels   (memory/, ops/)
"""

from sparkrdma_tpu.conf import TpuShuffleConf
from sparkrdma_tpu.utils.columns import ColumnBatch
from sparkrdma_tpu.utils.types import (
    BlockLocation,
    BlockManagerId,
    ShuffleManagerId,
)

__version__ = "0.1.0"

__all__ = [
    "TpuShuffleConf",
    "ColumnBatch",
    "BlockLocation",
    "BlockManagerId",
    "ShuffleManagerId",
    "__version__",
]
