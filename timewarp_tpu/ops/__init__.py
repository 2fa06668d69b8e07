"""Numeric building blocks of the batched engines (see numeric.py for
the native-layer design stance)."""

from .numeric import (I32MAX, compress_lanes, expand_lanes, fill_holes,
                      free_bits, group_rank, nth_set_bit, thi, tlo, u32sum)

__all__ = ["I32MAX", "group_rank", "free_bits", "nth_set_bit",
           "fill_holes", "expand_lanes", "compress_lanes", "u32sum", "tlo",
           "thi"]
