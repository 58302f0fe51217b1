"""``indexer_device_share``: the share of the device's busy time under the
scopes ``indexer`` (the index keys' write and the scoring of every position
of a token's context, through the page table) and ``index_topk`` (the
exact top-k of the scores and the count of the pages it lands in) in the
full layers of a latent-attention model.  The scoring is not a kernel:
this is its cost, not its roofline."""
from benchmark import scope_share


def read(run):
    return scope_share.share(run, under=("indexer", "index_topk"))
