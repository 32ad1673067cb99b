"""openembedding_tpu — TPU-native framework for massive sparse-embedding models.

A from-scratch JAX/XLA/Pallas re-design with the capabilities of the
OpenEmbedding reference (distributed parameter server for sparse embedding
tables accelerating recommendation-model training): model-parallel embedding
tables sharded across TPU HBM over a device mesh, data-parallel dense nets,
row-sparse server-style optimizers, hash-table embeddings for unbounded key
spaces, sharded checkpoint/restore incl. optimizer state, dense model export,
and a serving path — all inside single SPMD programs instead of RPC.

Layer map (TPU-native analogue of reference SURVEY.md §1):
  models/    example model zoo (LR, WDL, DeepFM, xDeepFM, DCN) — reference L7
  embedding  high-level Embedding API + train-step builder        — reference L6
  table      single-shard pull/apply core                         — reference L1/L2
  ops/       dedup, hash probing, Pallas kernels                  — reference L5 kernels
  parallel/  mesh sharding, collectives, sharded tables           — reference L3/L-PS/L-CORE
  checkpoint sharded dump/load with model_meta JSON               — reference dump/load operators
"""

__version__ = "0.1.0"

from .meta import (EmbeddingVariableMeta, ModelMeta, ModelVariableMeta,
                   UNBOUNDED_VOCAB, META_FORMAT_VERSION)
from .table import TableState, create_table, pull, apply_gradients
from .hash_table import HashTableState, create_hash_table
from .optim.optimizers import make_optimizer, SparseOptimizer
from .optim.initializers import make_initializer, Initializer
from .embedding import EmbeddingSpec, EmbeddingCollection
from .fused import FusedMapper, make_fused_specs
from .hybrid import (DenseEmbeddings, DenseFeatureSpec, HybridModel,
                     split_sparse_dense)
from .ragged import pad_ragged, pad_id_for, pool_rows
from .offload import HostOffloadedTable, ShardedOffloadedTable
from .dirty import DirtyTracker
from . import distributed
from .training import Trainer, TrainState, binary_logloss
from .analysis import retrace as _retrace

# the load ledger listens from here on: every program any entry point
# traces, lowers, fetches or compiles is on it (analysis/retrace.py)
_retrace.install()
