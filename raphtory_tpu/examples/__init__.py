"""User-space examples — capability parity with the reference's
``SRC/examples/`` tree (SURVEY §2.8): one module per domain, each exposing
Source/Parser pairs (the reference's Spout/Router split) and the domain
analysers built on the core algorithm library.

| Reference domain | Module |
|---|---|
| ``examples/random``          | :mod:`.random_graph` |
| ``examples/gab``             | :mod:`.gab` |
| ``examples/blockchain``      | :mod:`.blockchain` |
| ``examples/ldbc``            | :mod:`.ldbc` |
| ``examples/citationNetwork`` | :mod:`.citations` |
| ``examples/trackAndTrace``   | :mod:`.track_and_trace` |
| ``examples/twitterRumour``   | :mod:`.twitter_rumour` |
"""

from .blockchain import (
    BitcoinBlockParser,
    ChainalysisABParser,
    DashcoinBlockParser,
    EthereumDegreeRanking,
    EthereumTaintTracking,
    EthereumTransactionParser,
    LitecoinBlockParser,
)
from .citations import CitationParser
from .gab import (GabMostUsedTopics, GabPostGraphParser,
                  GabRawPostParser, GabUserGraphParser)
from .ldbc import LDBCParser
from .random_graph import RandomCommandSource, RandomJsonParser
from .track_and_trace import TrackAndTraceParser, location_id
from .twitter_rumour import RumourParser

__all__ = [
    "RandomCommandSource",
    "RandomJsonParser",
    "GabRawPostParser",
    "GabUserGraphParser",
    "GabPostGraphParser",
    "GabMostUsedTopics",
    "LitecoinBlockParser",
    "EthereumTransactionParser",
    "EthereumTaintTracking",
    "EthereumDegreeRanking",
    "BitcoinBlockParser",
    "ChainalysisABParser",
    "DashcoinBlockParser",
    "LDBCParser",
    "CitationParser",
    "TrackAndTraceParser",
    "location_id",
    "RumourParser",
]
