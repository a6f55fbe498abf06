"""As ``attn_device_ms.train``, for ``tl.embed`` + ``tl.head`` +
``tl.loss``: the embedding lookups, the final norm and unembedding
matmul, and the loss from the logits."""

from benchmark import spans


def read(run):
    return spans.group_ms(run, "head_loss")
