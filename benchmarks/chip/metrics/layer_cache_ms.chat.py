"""The KV cache's plumbing in the decode program, ms a step: self time
of the ops under the named scope ``kv_write`` and of those under
``layers`` outside ``attn`` and ``ffn`` (the layer scan's slices and
write-backs of the stacked cache and weights), mean over the chips
and the traced steps (``spans.reduce``).  Moves ``itl_p99_ms``."""


def read(run):
    return run.spans.get("layer_cache_ms")
