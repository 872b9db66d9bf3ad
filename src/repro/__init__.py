"""repro: bit-reproducible floating-point aggregation for JAX training and
inference at multi-pod scale (Mueller et al., ICDE'18, adapted to TPU)."""
from repro.core import (  # noqa: F401
    ReproSpec, ReproAcc, from_values, finalize, merge, segment_rsum,
    repro_psum,
)
from repro.ops import groupby_agg, plan_groupby, sharded_groupby_agg  # noqa: F401,E501

__version__ = "1.0.0"
