from . import spans
from .decode_loop import (DEFAULT_MAX_DEPTH, DEFAULT_SPEC_HISTORY,
                          SPEC_DEPTH_CANDIDATES, draft_from_history,
                          make_fused_decode_step, make_lane_step,
                          make_paged_spec_decode_step,
                          make_spec_decode_step, masked_merge)
from .engine import (ServeEngine, make_decode_step, make_prefill_step,
                     prefill_segments)
from .frontend import (QueueFullError, RequestRecord, ServeFrontend,
                       TokenStream)
from .kv_cache import CacheLayoutError, SlotKVCachePool, SlotOverflowError
from .loadgen import (GENERATORS, SLOModel, TraceRequest, bursty_trace,
                      heavy_tailed_trace, materialize, poisson_trace,
                      shared_prefix_trace, templated_trace, trace_summary)
from .scheduler import (TERMINAL_STATES, PromptTooLongError, Request,
                        RequestState, ServeScheduler, TickRecord,
                        percentile)

__all__ = [
    "ServeEngine", "make_decode_step", "make_prefill_step",
    "prefill_segments",
    "SlotKVCachePool", "SlotOverflowError", "CacheLayoutError",
    "ServeScheduler", "Request", "RequestState", "TickRecord",
    "percentile", "PromptTooLongError", "TERMINAL_STATES",
    "ServeFrontend", "TokenStream", "RequestRecord", "QueueFullError",
    "SLOModel", "TraceRequest", "GENERATORS", "poisson_trace",
    "bursty_trace", "heavy_tailed_trace", "shared_prefix_trace",
    "templated_trace", "materialize", "trace_summary",
    "DEFAULT_MAX_DEPTH", "make_fused_decode_step", "make_lane_step",
    "masked_merge",
    "DEFAULT_SPEC_HISTORY", "SPEC_DEPTH_CANDIDATES",
    "draft_from_history", "make_spec_decode_step",
    "make_paged_spec_decode_step",
    "spans",
]
