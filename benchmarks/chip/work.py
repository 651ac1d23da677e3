"""The least work each measured kernel's algorithm needs, from the shapes
of what it was given — never from what the kernel happens to do, so a
roofline share stays comparable when the implementation changes."""
from __future__ import annotations


def lorenzo_bytes(cells: int) -> int:
    """Prequant + 3-D Lorenzo codes: each brick cell is read once as
    float32 and its code written once as int32."""
    return 8 * int(cells)


def huffman_decode_bytes(codes: int, bits_per_code: float) -> float:
    """Canonical Huffman decode: each payload bit is read once and each
    code written once as int32."""
    return codes * (bits_per_code / 8.0 + 4.0)


def roofline_share(nbytes: float, flops: float, seconds: float,
                   peaks: dict) -> float | None:
    """Least time the chip needs for the work (the larger of its bytes
    over peak bandwidth and its operations over peak rate) over the
    measured device time, in percent; None without device time."""
    if seconds <= 0:
        return None
    least = max(nbytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
