"""Query-by-Sketch core: the paper's contribution as composable JAX modules."""
from .graph import (
    INF,
    Graph,
    barabasi_albert_graph,
    from_edges,
    gnp_random_graph,
    grid_graph,
    random_regular_graph,
    largest_connected_component,
    ring_of_cliques,
    select_landmarks,
    to_networkx,
)
from .frontier import FrontierEngine, HubSplit, make_relay, segment_or
from .labelling import LabellingScheme, build_labelling, labelling_size_bytes, meta_apsp
from .packing import (
    PackedLabels,
    pack_bits,
    pack_labelling,
    packed_size_bytes,
    unpack_bits,
    widen_dist,
)
from .distributed import ShardedLabels, distributed_build_sharded
from .qbs import QbSIndex, SPGResult
from .search import Query, SearchContext, SearchResult, make_search_context, search_chunk
from .sharded import ShardedIndex
from .sketch import SketchBatch, compute_sketch_batch, d_top_only

__all__ = [
    "INF",
    "Graph",
    "barabasi_albert_graph",
    "from_edges",
    "gnp_random_graph",
    "grid_graph",
    "random_regular_graph",
    "largest_connected_component",
    "ring_of_cliques",
    "select_landmarks",
    "to_networkx",
    "FrontierEngine",
    "HubSplit",
    "make_relay",
    "segment_or",
    "make_search_context",
    "LabellingScheme",
    "build_labelling",
    "labelling_size_bytes",
    "meta_apsp",
    "PackedLabels",
    "pack_bits",
    "pack_labelling",
    "packed_size_bytes",
    "unpack_bits",
    "widen_dist",
    "QbSIndex",
    "ShardedIndex",
    "ShardedLabels",
    "distributed_build_sharded",
    "SPGResult",
    "Query",
    "SearchContext",
    "SearchResult",
    "search_chunk",
    "SketchBatch",
    "compute_sketch_batch",
    "d_top_only",
]
