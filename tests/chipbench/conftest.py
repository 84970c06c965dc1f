"""The tiny CPU shape of each configuration this directory's tests cut the
benchmark to, beyond those ``chipbench_testlib.TINY_SHAPES`` lists: a new
configuration registers its own here, and the shared table's tests
(``tiny_root``, the control and drive tests) pick it up."""

from chipbench_testlib import TINY_SHAPES

#: DeepSeek-V3's routing at a size the Pallas interpreter runs in seconds:
#: 8 groups of 4 experts, top-4 groups, 8 experts per token, 4 held here
TINY_SHAPES.setdefault("deepseek-v3-moe-f32", {"shape": {
    "N": 128, "d": 256, "m": 128, "E": 32, "E_held": 4, "k": 8,
    "dtype": "float32"}})
