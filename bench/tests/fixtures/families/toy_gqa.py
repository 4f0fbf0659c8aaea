"""A model family the harness has never heard of, for the lookup test.

It is the dense family under another name, and counts each call the
harness makes into it: the key map, the weights' ``draw``, the reference
and the operation counts the readers use."""
from families import dense_gqa

CALLS = {}


def _counted(name, fn):
    def wrapper(*args, **kwargs):
        CALLS[name] = CALLS.get(name, 0) + 1
        return fn(*args, **kwargs)
    return wrapper


model_config = _counted("model_config", dense_gqa.model_config)
forward_rows = _counted("forward_rows", dense_gqa.forward_rows)
draw = _counted("draw", lambda name, shape, dtype, key: None)
token_flops = _counted("token_flops", dense_gqa.token_flops)
prompt_flops = _counted("prompt_flops", dense_gqa.prompt_flops)
paged_attn_flops = _counted("paged_attn_flops", dense_gqa.paged_attn_flops)
paged_attn_bytes = _counted("paged_attn_bytes", dense_gqa.paged_attn_bytes)
