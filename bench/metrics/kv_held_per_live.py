"""Paged KV: tokens of KV the pool holds (``kv.blocks_used`` x block size)
over the tokens in-flight requests have (prompt plus delivered, counted
by the harness), averaged over the pumps of the traced span."""


def read(record):
    bs = record["block_size"]
    ratios = [p.blocks_used * bs / p.live_tokens for p in record["pumps"]
              if p.live_tokens]
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
