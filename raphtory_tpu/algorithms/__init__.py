"""Built-in algorithm library — parity with the reference's
``core/analysis/Algorithms/`` plus the example-space analysers (SURVEY §2.8):
ConnectedComponents, DegreeBasic/DegreeRanking, PageRank, BinaryDiffusion,
FlowGraph, Density, temporal TaintTracking (EthereumTaintTracking),
BFS/SSSP (LDBC bar), LDBC Graphalytics' CDLP and LCC, and SGC's
feature propagation.

Seven of them have a columnar kind (``engine/hopbatch.py``: every (hop,
window) view of a Range a column of one dispatch): PageRank
(``pagerank``), ConnectedComponents (``cc``), BFS and unit-weight SSSP
(``bfs``), weighted SSSP (``bfs`` with a weight state), CDLP (``cdlp``),
LCC (``lcc``), which has no other engine, and SGC (``sgc``), whose state
is a row of features a vertex and which the job layer serves there
alone."""

from .clustering import LCC
from .connected_components import ConnectedComponents
from .degree import DegreeBasic
from .diffusion import BinaryDiffusion
from .flow import FlowGraph
from .lpa import CDLP, LabelPropagation
from .pagerank import PageRank
from .propagation import SGC
from .rankings import DegreeRanking, Density, StarNode
from .taint import TaintTracking
from .traversal import BFS, SSSP

__all__ = [
    "ConnectedComponents",
    "DegreeBasic",
    "DegreeRanking",
    "Density",
    "StarNode",
    "BinaryDiffusion",
    "FlowGraph",
    "LabelPropagation",
    "CDLP",
    "LCC",
    "PageRank",
    "SGC",
    "TaintTracking",
    "BFS",
    "SSSP",
]
