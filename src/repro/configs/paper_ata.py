"""The paper's own workload: C = A^t A gram multiplication.

Sizes from §6.2 (n = 5000, 10000, P in {6,12,18,38,76,114,250}) plus
production-scale cells for the TPU dry-run (the paper's technique as the
distributed Shampoo/normal-equations primitive at pod scale).
"""
from dataclasses import dataclass
from typing import Tuple, Union

# Paper experiment grid (CPU wall-clock reproduction, Figs 5-8)
PAPER_NS = (5000, 10000)
PAPER_PS = (6, 12, 18, 38, 76, 114, 250)
COMPLETE_LEVEL_PS = (6, 38, 250)        # P = npl(l): complete parallel levels
PAPER_MAX_SPEEDUP = 64.28               # Fig 6, n=10000, P=250
PAPER_EFFICIENCY_RANGE = (0.26, 0.66)   # Fig 7
PAPER_BASE_CASE = 32                    # Alg 1 leaf on CPU
PAPER_COMM_FRACTION = (0.0014, 0.0046)  # §6.3.2 (P=6 .. P=250)


@dataclass(frozen=True)
class GramCell:
    """One distributed-gram dry-run cell: A (m, n) sharded on the mesh.

    ``levels="auto"`` (the default) resolves per shard as ``ata`` does:
    the fused executor's cheapest depth by its traffic model, or the
    reference recursion's natural depth (``ata_levels_for`` /
    ``strassen_levels_for``, capped at ``strassen.AUTO_MAX_LEVELS``).
    """
    name: str
    m: int
    n: int
    scheme: str = "allreduce"            # allreduce | reducescatter | ring
    levels: Union[int, str] = "auto"
    dtype: str = "bfloat16"


# Production-mesh gram cells (dry-run + roofline for the paper's technique).
# gram_64k* are one workload under four treatments — the §Perf cell-C
# hillclimb: paper-faithful allreduce -> reduce-scatter -> half-ring, and
# classical (levels=0) vs Strassen compute.
GRAM_CELLS = {
    "gram_64k": GramCell("gram_64k", m=262144, n=65536),
    "gram_64k_l0": GramCell("gram_64k_l0", m=262144, n=65536, levels=0),
    "gram_64k_rs": GramCell("gram_64k_rs", m=262144, n=65536,
                            scheme="reducescatter"),
    "gram_64k_ring": GramCell("gram_64k_ring", m=262144, n=65536,
                              scheme="ring"),
    "gram_16k_rs": GramCell("gram_16k_rs", m=1048576, n=16384,
                            scheme="reducescatter"),
}
