"""The jaxpr census helpers of the fusion suites; the walk itself lives
in the package (amgx_tpu/telemetry/census.py) so chip_smoke.py counts
the same way the tests do."""
from amgx_tpu.telemetry.census import (  # noqa: F401
    KERNEL_KEYS, KERNEL_NAME_RE, full_vector_reductions, kernel_counts,
    kernel_names, outer_prims, slab_consts, subjaxprs)
