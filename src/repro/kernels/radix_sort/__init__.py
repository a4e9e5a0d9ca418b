from repro.kernels.radix_sort.ops import (
    bucket_argsort,
    bucket_argsort_jax,
    radix_argsort_jax,
)

__all__ = ["bucket_argsort", "bucket_argsort_jax", "radix_argsort_jax"]
