"""prefill_token_share.ttft: share of the tokens through the model in the
window that were prefill.
"""
from harness import metric_lib


def read(ctx):
    return metric_lib.share_of_counts(
        ctx, ("prefill_tokens",), ("prefill_tokens", "decode_tokens"))
