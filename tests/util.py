"""Shared test helpers."""

from __future__ import annotations

from tokenizers import Tokenizer, models, pre_tokenizers

from dynamo_tpu.model_card import ModelDeploymentCard
from dynamo_tpu.tokenizer import TokenizerWrapper

TEST_WORDS = (
    "hello world the quick brown fox jumps over lazy dog a b c d e f g "
    "STOP assistant user im_start im_end one two three four five six"
).split()


def layer_caches(shape, dtype):
    """A zeroed paged cache as the step programs take it: one
    [Hkv, num_blocks, block_size, D] array per layer. `shape` is
    (num_layers, Hkv, num_blocks, block_size, D)."""
    from dynamo_tpu.ops.kv_quant import make_cache

    return make_cache(shape[0], shape[1:], dtype, quantized=False)


def make_test_tokenizer() -> TokenizerWrapper:
    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
    for w in TEST_WORDS:
        vocab.setdefault(w, len(vocab))
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    return TokenizerWrapper(tok, eos_token_ids=[2])


def make_test_mdc(name: str = "test-model", **kwargs) -> ModelDeploymentCard:
    return ModelDeploymentCard.from_tokenizer(
        name, make_test_tokenizer(), **kwargs
    )
