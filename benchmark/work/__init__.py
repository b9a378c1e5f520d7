"""Work functions: the bytes and operations a fit needs, from shapes, one
file per configuration; what they share is here."""


def least_seconds(n_bytes: int, n_ops: int, peaks: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    by_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    by_ops = n_ops / peaks["flops_per_s"]
    return max(by_bytes, by_ops), ("bytes" if by_bytes >= by_ops else "ops")
