"""dbcsr_tpu — a TPU-native distributed block-sparse matrix framework.

A ground-up JAX/XLA/Pallas re-design with the capabilities of DBCSR
(CP2K's Distributed Block Compressed Sparse Row library; reference
`README.md:13-15`): distributed block-sparse matrix-matrix multiplication
and supporting operations, a tall-and-skinny (TAS) layer, and an n-rank
block-sparse tensor-contraction layer.

This is NOT a port.  Design mapping (reference concept -> here):

* Fortran BCSR index + typed data areas  ->  host NumPy block index +
  per-block-shape device arrays in HBM (`dbcsr_tpu.core.matrix`).
* libsmm_acc JIT'd CUDA batched small-GEMM kernels
  (`src/acc/libsmm_acc/libsmm_acc.cpp`)  ->  XLA/Pallas batched SMM over
  integer parameter stacks (`dbcsr_tpu.acc`).
* MPI Cannon metronome loop (`src/mm/dbcsr_mm_cannon.F:1345`)  ->
  `shard_map` over a 2D `jax.sharding.Mesh` with `lax.ppermute` ring
  shifts (`dbcsr_tpu.parallel`).
* OpenMP threads / per-thread work matrices  ->  vectorized device work;
  no host threading needed.
"""

import time as _time

_t_import = _time.perf_counter()  # the span `import` starts here

from dbcsr_tpu.core.kinds import (
    dbcsr_type_real_4,
    dbcsr_type_real_8,
    dbcsr_type_complex_4,
    dbcsr_type_complex_8,
    dtype_of,
)
from dbcsr_tpu.core.config import (
    get_config,
    get_default_config,
    print_config,
    set_config,
)
from dbcsr_tpu.core.lib import (
    finalize_lib,
    init_lib,
    place_compile_cache,
    print_statistics,
    steady_host_allocator,
)
from dbcsr_tpu.core.dist import (
    ProcessGrid,
    Distribution,
    convert_offsets_to_sizes,
    convert_sizes_to_offsets,
    dist_bin,
)
from dbcsr_tpu.core.matrix import BlockIterator, BlockSparseMatrix, create
from dbcsr_tpu.core import mempool
from dbcsr_tpu.core.mempool import chain
from dbcsr_tpu.mm.multiply import multiply
from dbcsr_tpu import obs
from dbcsr_tpu import resilience
from dbcsr_tpu.ops.operations import (
    FUNC_ARTANH,
    FUNC_ASIN,
    FUNC_COS,
    FUNC_DDSIN,
    FUNC_DDTANH,
    FUNC_DSIN,
    FUNC_DTANH,
    FUNC_INVERSE,
    FUNC_INVERSE_SPECIAL,
    FUNC_SIN,
    FUNC_SPREAD_FROM_ZERO,
    FUNC_TANH,
    FUNC_TRUNCATE,
    add,
    add_on_diag,
    clear,
    column_norms,
    copy,
    copy_into_existing,
    crop_matrix,
    dot,
    filter_matrix,
    frobenius_norm,
    function_of_elements,
    gershgorin_norm,
    get_block_diag,
    hadamard_product,
    maxabs_norm,
    reserve_all_blocks,
    reserve_blocks,
    reserve_diag_blocks,
    scale,
    scale_by_vector,
    set_diag,
    set_value,
    get_diag,
    trace,
    triu,
    verify_matrix,
)
from dbcsr_tpu.ops.transformations import (
    desymmetrize,
    new_transposed,
    redistribute,
    submatrix,
)
from dbcsr_tpu.ops.csr import (
    CSR_DBCSR_BLKROW_DIST,
    CSR_EQROW_CEIL_DIST,
    CSR_EQROW_FLOOR_DIST,
    CsrMatrix,
    complete_redistribute,
    csr_create_from_matrix,
    csr_from_matrix,
    csr_print_sparsity,
    csr_write,
    matrix_from_csr,
    to_csr_filter,
)
from dbcsr_tpu.ops.io import binary_read, binary_write, print_block_sum, print_matrix
from dbcsr_tpu.ops.test_methods import (
    checksum,
    from_dense,
    make_random_matrix,
    reset_randmat_seed,
    to_dense,
)
from dbcsr_tpu.ops.tests import TEST_BINARY_IO, TEST_MM, run_tests
# ref dbcsr_replicate_all (`dbcsr_transformations.F:108`); the paired
# dbcsr_sum_replicated merge is a lax.psum inside shard_map here (see
# parallel/dist_matrix.py:replicate docstring)
from dbcsr_tpu.parallel.dist_matrix import replicate as replicate_all

__version__ = "0.1.0"

place_compile_cache()
steady_host_allocator()

# the public surface (~88 symbols; the dbcsr_api.F analog list,
# see PARITY.md for the name-by-name mapping)
__all__ = [
    "BlockIterator",
    "BlockSparseMatrix",
    "CSR_DBCSR_BLKROW_DIST",
    "CSR_EQROW_CEIL_DIST",
    "CSR_EQROW_FLOOR_DIST",
    "CsrMatrix",
    "Distribution",
    "FUNC_ARTANH",
    "FUNC_ASIN",
    "FUNC_COS",
    "FUNC_DDSIN",
    "FUNC_DDTANH",
    "FUNC_DSIN",
    "FUNC_DTANH",
    "FUNC_INVERSE",
    "FUNC_INVERSE_SPECIAL",
    "FUNC_SIN",
    "FUNC_SPREAD_FROM_ZERO",
    "FUNC_TANH",
    "FUNC_TRUNCATE",
    "ProcessGrid",
    "TEST_BINARY_IO",
    "TEST_MM",
    "add",
    "add_on_diag",
    "binary_read",
    "binary_write",
    "checksum",
    "clear",
    "column_norms",
    "complete_redistribute",
    "convert_offsets_to_sizes",
    "convert_sizes_to_offsets",
    "copy",
    "copy_into_existing",
    "chain",
    "create",
    "mempool",
    "crop_matrix",
    "csr_create_from_matrix",
    "csr_from_matrix",
    "csr_print_sparsity",
    "csr_write",
    "dbcsr_type_complex_4",
    "dbcsr_type_complex_8",
    "dbcsr_type_real_4",
    "dbcsr_type_real_8",
    "desymmetrize",
    "dist_bin",
    "dot",
    "dtype_of",
    "filter_matrix",
    "finalize_lib",
    "frobenius_norm",
    "from_dense",
    "function_of_elements",
    "gershgorin_norm",
    "get_block_diag",
    "get_config",
    "get_default_config",
    "get_diag",
    "hadamard_product",
    "init_lib",
    "make_random_matrix",
    "matrix_from_csr",
    "maxabs_norm",
    "multiply",
    "new_transposed",
    "obs",
    "resilience",
    "print_block_sum",
    "print_config",
    "print_matrix",
    "print_statistics",
    "redistribute",
    "replicate_all",
    "reserve_all_blocks",
    "reserve_blocks",
    "reserve_diag_blocks",
    "reset_randmat_seed",
    "run_tests",
    "scale",
    "scale_by_vector",
    "set_config",
    "set_diag",
    "set_value",
    "submatrix",
    "to_csr_filter",
    "to_dense",
    "trace",
    "triu",
    "verify_matrix",
]

# top to bottom of this file, as the timer table's span `import`
# (`core.timings` did not exist when it began, so it is booked here)
from dbcsr_tpu.core import timings as _timings

_timings.book("import", _time.perf_counter() - _t_import)
