"""The roofline bound: the least time a chip with these peaks could take."""


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_bf16"],
               nbytes / peaks["hbm_bytes_per_s"])


def share(flops: float, nbytes: float, seconds: float,
          peaks: dict) -> float:
    """Percent of the roofline reached (100 = as fast as the peaks allow)."""
    return 100.0 * least_seconds(flops, nbytes, peaks) / seconds
