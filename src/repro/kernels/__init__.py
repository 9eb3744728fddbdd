# Pallas TPU kernels for the pipeline's compute hot spots:
#   minplus/ — dense-block min-plus semiring matmul (transitive reduction)
#   xdrop/   — banded x-drop alignment wavefront (pairwise alignment)
#   pileup/  — banded pileup accumulation + majority vote (consensus)
#   cc/      — fused hook/shortcut connected-components rounds
#   spgemm/  — fused ring-SUMMA local SpGEMM stage batches (overlap stage)
# Validated on CPU via interpret=True against the pure-jnp oracles (ref.py);
# tests/test_chip_compile.py lowers each through the TPU compiler for v5e.
# Importing this package registers every kernel (and its oracle) with the
# backend dispatch layer in core/backend.py.
from .cc import cc_labels_pallas, cc_labels_ref  # noqa: F401
from .minplus import minplus_matmul, minplus_matmul_ref  # noqa: F401
from .pileup import pileup_vote, pileup_vote_ref  # noqa: F401
from .spgemm import (  # noqa: F401
    spgemm_ring_stages_pallas,
    spgemm_ring_stages_ref,
)
from .xdrop import xdrop_extend_batch, xdrop_extend_batch_ref  # noqa: F401
