"""GPT-2 — the flagship language model (reference capability target:
BASELINE.md config 4, "GPT-2 345M ... fused attention/FFN"; the reference's
closest in-tree models are fleet's GPT test models,
python/paddle/fluid/tests/unittests/auto_parallel_gpt_model.py).

TPU-first design:
* pre-LN transformer, bf16-friendly, weight-tied logits
* attention via F.scaled_dot_product_attention -> Pallas flash kernel
* Megatron sharding ANNOTATIONS baked into the parameters (pspec): qkv/fc1
  column-sharded on 'mp', out-proj/fc2 row-sharded, embeddings vocab-sharded;
  activations constrained to ('dp', 'sep', None) so sequence parallelism
  shards the token axis.  Under pjit these annotations are the whole
  distribution strategy (GSPMD inserts the collectives the reference's
  mp_layers/c_* ops hand-coded).
* vocab padded to a multiple of 128 so the logits matmul tiles the MXU.
"""
from __future__ import annotations

import dataclasses
import math

from jax.sharding import PartitionSpec

from .. import ops
from ..core.dispatch import call
from ..core.tensor import Tensor
from ..distributed import mp_overlap as _mpo
from ..distributed.mp_layers import shard_heads, with_sharding_constraint
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import LayerNorm
from ..observability import scopes as _scopes


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304          # 50257 padded to 128-multiple (MXU tiling)
    max_position_embeddings: int = 1024
    hidden_size: int = 1024
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    intermediate_size: int = 4096
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True
    # activation recompute per block (jax.checkpoint): trades ~1/3 more
    # FLOPs for O(sqrt)-ish activation memory — required for long-sequence
    # training (s=8192 without it sits at the 16GB HBM edge on one v5e)
    use_recompute: bool = False

    @classmethod
    def gpt2_small(cls):
        return cls(hidden_size=768, num_hidden_layers=12,
                   num_attention_heads=12, intermediate_size=3072)

    @classmethod
    def gpt2_medium(cls):  # the 345M benchmark config
        return cls(hidden_size=1024, num_hidden_layers=24,
                   num_attention_heads=16, intermediate_size=4096)

    @classmethod
    def gpt2_large(cls):
        return cls(hidden_size=1280, num_hidden_layers=36,
                   num_attention_heads=20, intermediate_size=5120)

    @classmethod
    def tiny(cls):  # for tests
        return cls(vocab_size=512, max_position_embeddings=128,
                   hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=128,
                   hidden_dropout_prob=0.0, attention_dropout_prob=0.0)


class GPTAttention(Layer):
    _scope = _scopes.ATTN

    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        self.hidden_size = c.hidden_size
        init = I.Normal(0.0, c.initializer_range)
        out_init = I.Normal(0.0, c.initializer_range
                            / math.sqrt(2 * c.num_hidden_layers))
        self.qkv_proj = Linear(c.hidden_size, 3 * c.hidden_size)
        self.qkv_proj.weight.set_value(Tensor(init((c.hidden_size,
                                                    3 * c.hidden_size))))
        self.out_proj = Linear(c.hidden_size, c.hidden_size)
        self.out_proj.weight.set_value(Tensor(out_init((c.hidden_size,
                                                        c.hidden_size))))
        self.attn_dropout_p = c.attention_dropout_prob
        self.resid_dropout = Dropout(c.hidden_dropout_prob)
        # Megatron layout: qkv column-sharded, out row-sharded
        self.qkv_proj.weight.pspec = PartitionSpec(None, "mp")
        self.qkv_proj.bias.pspec = PartitionSpec("mp")
        self.out_proj.weight.pspec = PartitionSpec("mp", None)

    def _out_projection(self, out):
        # row-sharded projection: overlapped ⇒ the matmul→all-reduce runs
        # as the ring (partial-accumulate + chunked permute) island; off
        # ⇒ today's GSPMD lowering through the Linear
        if _mpo.row_viable(self.hidden_size):
            return call(
                lambda o, w, bb: _mpo.row_parallel_matmul(o, w, bb),
                out, self.out_proj.weight, self.out_proj.bias,
                name="mp_overlap_row")
        return self.out_proj(out)

    def forward(self, x, cache=None):
        b, s, _ = x.shape
        h = self.hidden_size
        static_cache = (cache is not None
                        and not isinstance(cache, (tuple, list)))
        if static_cache and _mpo.qkv_viable(self.num_heads, self.head_dim):
            # overlapped fused-qkv: column projection + 3-ppermute head
            # re-deal in one island — replaces GSPMD's per-layer
            # all-to-all/all-gather reshard from the 3H/tp shard
            # boundary to the head boundary (PR 11's named follow-up)
            nh, hd = self.num_heads, self.head_dim
            q, k, v = call(
                lambda xr, w, bb: _mpo.qkv_heads(xr, w, bb, nh, hd),
                x, self.qkv_proj.weight, self.qkv_proj.bias,
                name="mp_overlap_qkv")
        else:
            qkv = self.qkv_proj(x)
            if cache is None and F.packed_attention_supported(
                    qkv, self.num_heads, self.attn_dropout_p, self.training):
                # the flash kernels read q, k and v where the projection
                # wrote them (block index maps onto the fused buffer)
                out = F.packed_attention(qkv, self.num_heads, is_causal=True)
                out = ops.reshape(out, [b, s, self.hidden_size])
                return self.resid_dropout(self._out_projection(out))
            # q/k/v as contiguous LAST-DIM slices of the fused projection:
            # reshape-to-(b,s,3,h,d)+unbind forces a transposed-layout copy
            # of the whole qkv activation per layer (~0.1 ms × 24 layers ×
            # fwd+bwd on the 345M bench).  Last-dim slices are free for an
            # XLA consumer, which reads them in place (the cached and the
            # reference attention, the GEMMs of the backward); a Mosaic
            # kernel cannot take a slice as an operand, so in front of the
            # flash kernel they are one three-output pass over the buffer
            # (201 MB a layer at 16 x 1,024 x 1,024) — hence the branch above
            q = ops.reshape(qkv[:, :, :h],
                            [b, s, self.num_heads, self.head_dim])
            k = ops.reshape(qkv[:, :, h:2 * h],
                            [b, s, self.num_heads, self.head_dim])
            v = ops.reshape(qkv[:, :, 2 * h:],
                            [b, s, self.num_heads, self.head_dim])
        if static_cache:
            # static slotted cache (serving.cache view): append into the
            # preallocated buffers + length-masked attention — one shape
            # for the life of the process, no per-token retrace.  Under a
            # tensor-parallel serving mesh the q/k/v activations are
            # pinned head-sharded so the cached attention (and the pool
            # scatter) stays device-local (no-op without an 'mp' mesh)
            q, k, v = shard_heads(q), shard_heads(k), shard_heads(v)
            out = cache.attend(q, k, v)
            out = ops.reshape(out, [b, s, self.hidden_size])
            return self.resid_dropout(self._out_projection(out)), cache
        if cache is not None:
            # LEGACY CONCAT SHIM (see GPTForCausalLM.gen_legacy_concat_cache)
            pk, pv = cache
            k = ops.concat([pk, k], axis=1)
            v = ops.concat([pv, v], axis=1)
            cache = (k, v)
        # always causal: the reference SDPA mask is end-aligned
        # (tril offset sk-sq), which is exactly right for cached decode —
        # each new token sees the full past plus itself, never its future
        out = F.scaled_dot_product_attention(
            q, k, v, dropout_p=self.attn_dropout_p, is_causal=True,
            training=self.training)
        out = ops.reshape(out, [b, s, self.hidden_size])
        out = self.resid_dropout(self._out_projection(out))
        if cache is not None:
            return out, cache
        return out


class GPTMLP(Layer):
    _scope = _scopes.MLP

    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        init = I.Normal(0.0, c.initializer_range)
        out_init = I.Normal(0.0, c.initializer_range
                            / math.sqrt(2 * c.num_hidden_layers))
        self.fc1 = Linear(c.hidden_size, c.intermediate_size)
        self.fc1.weight.set_value(Tensor(init((c.hidden_size,
                                               c.intermediate_size))))
        self.fc2 = Linear(c.intermediate_size, c.hidden_size)
        self.fc2.weight.set_value(Tensor(out_init((c.intermediate_size,
                                                   c.hidden_size))))
        self.dropout = Dropout(c.hidden_dropout_prob)
        self.fc1.weight.pspec = PartitionSpec(None, "mp")
        self.fc1.bias.pspec = PartitionSpec("mp")
        self.fc2.weight.pspec = PartitionSpec("mp", None)

    def forward(self, x):
        a = F.gelu(self.fc1(x), approximate=True)
        if _mpo.row_viable(self.fc2.weight.shape[0]):
            # overlapped row matmul (ring in fwd, shard-local bwd via the
            # custom_vjp); off ⇒ GSPMD's monolithic all-reduce
            out = call(
                lambda o, w, bb: _mpo.row_parallel_matmul(o, w, bb),
                a, self.fc2.weight, self.fc2.bias, name="mp_overlap_row")
        else:
            out = self.fc2(a)
        return self.dropout(out)


class GPTBlock(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln1 = LayerNorm(config.hidden_size, config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln2 = LayerNorm(config.hidden_size, config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)

    def forward(self, x, cache=None):
        if cache is not None:
            a, cache = self.attn(self.ln1(x), cache)
            x = x + a
        else:
            x = x + self.attn(self.ln1(x))
        x = x + self.mlp(self.ln2(x))
        # sequence-parallel activation layout: tokens sharded over 'sep'
        x = with_sharding_constraint(x, PartitionSpec("dp", "sep", None))
        if cache is not None:
            return x, cache
        return x


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        c = config
        init = I.Normal(0.0, c.initializer_range)
        self.wte = Embedding(c.vocab_size, c.hidden_size)
        self.wte.weight.set_value(Tensor(init((c.vocab_size, c.hidden_size))))
        self.wte.weight.pspec = PartitionSpec("mp", None)   # vocab-parallel
        self.wpe = Embedding(c.max_position_embeddings, c.hidden_size)
        self.wpe.weight.set_value(
            Tensor(init((c.max_position_embeddings, c.hidden_size))))
        self.drop = Dropout(c.hidden_dropout_prob)
        self.h = LayerList(
            [GPTBlock(c) for _ in range(c.num_hidden_layers)])
        self.ln_f = LayerNorm(c.hidden_size, c.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, cache=None):
        b, s = input_ids.shape
        finalize = False
        view = None
        if cache is not None and not isinstance(cache, (tuple, list)):
            from ..serving.cache import (DecodeView, PagedDecodeView,
                                         PagedKVCache, SlottedKVCache,
                                         is_cache_view)
            if isinstance(cache, SlottedKVCache):
                # bare cache state -> batched decode semantics; the caller
                # gets the advanced SlottedKVCache back
                cache = DecodeView(cache)
                finalize = True
            elif isinstance(cache, PagedKVCache):
                cache = PagedDecodeView(cache)
                finalize = True
            if not is_cache_view(cache):
                raise TypeError(
                    "cache must be a SlottedKVCache, a PagedKVCache, a "
                    "serving cache view, or the legacy per-layer (k, v) "
                    "tuple list; got %r" % (type(cache).__name__,))
            view = cache
        if position_ids is None:
            if view is not None:
                position_ids = Tensor(view.position_ids(b, s))
            else:
                start = 0 if cache is None else cache[0][0].shape[1]
                position_ids = ops.arange(start, start + s, dtype="int32")
                position_ids = ops.unsqueeze(position_ids, 0)
        if _mpo.embed_viable(self.config.vocab_size):
            # overlapped vocab-parallel lookup: masked local gather +
            # psum (activation-sized all-reduce) instead of GSPMD's
            # table-sized all-gather
            with _scopes.scope(_scopes.EMBED):
                tok = call(lambda ids, w: _mpo.vocab_embed(ids, w),
                           input_ids, self.wte.weight,
                           name="mp_overlap_embed")
            x = tok + self.wpe(position_ids)
        else:
            x = self.wte(input_ids) + self.wpe(position_ids)
        x = self.drop(x)
        x = with_sharding_constraint(x, PartitionSpec("dp", "sep", None))
        new_caches = []
        if self.config.use_recompute and self.training and cache is None:
            from ..distributed.recompute import recompute as _recompute
        else:
            _recompute = None
        for i, block in enumerate(self.h):
            if view is not None:
                x, _ = block(x, view)
            elif cache is not None:
                x, ci = block(x, cache[i])
                new_caches.append(ci)
            elif _recompute is not None:
                x = _recompute(block, x)
            else:
                x = block(x)
        x = self.ln_f(x)
        if view is not None:
            return x, (view.finalize() if finalize else view)
        if cache is not None:
            return x, new_caches
        return x


class GPTForCausalLM(Layer):
    """LM head with tied embeddings; loss computed from shifted logits."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)
            self.lm_head.weight.pspec = PartitionSpec(None, "mp")

    def _head(self, x):
        if not self.config.tie_word_embeddings:
            return self.lm_head(x)
        if _mpo.lm_viable(self.config.vocab_size):
            # overlapped LM head: rotate-weights ring over the vocab
            # shards — each step matmuls the resident shard into its
            # logits slice while the next is in flight (no monolithic
            # table all-gather)
            return call(lambda xr, w: _mpo.lm_head_matmul(xr, w),
                        x, self.gpt.wte.weight, name="mp_overlap_lm_head")
        return ops.matmul(x, self.gpt.wte.weight, transpose_y=True)

    def forward(self, input_ids, position_ids=None, cache=None):
        if cache is not None:
            x, cache = self.gpt(input_ids, position_ids, cache)
        else:
            x = self.gpt(input_ids, position_ids)
        with _scopes.scope(_scopes.LM_HEAD):
            logits = self._head(x)
        if cache is not None:
            return logits, cache
        return logits

    def gen_cache(self, batch_size, dtype="float32", max_len=None,
                  kv_dtype=None):
        """Preallocated static-shape slotted KV cache
        (``serving.cache.SlottedKVCache``): one decode program shape for
        the life of the process.  ``batch_size`` is the number of slots;
        ``max_len`` defaults to the model's position budget.
        ``kv_dtype="int8"`` stores the pool quantized (int8 codes +
        per-(row, head) f32 scales; appends quantize in-program and the
        decode attention dequantizes inline — ``dtype`` then only names
        the compute dtype the cache was built against)."""
        from ..serving.cache import SlottedKVCache
        c = self.config
        return SlottedKVCache.create(
            batch_size, c.num_hidden_layers,
            max_len or c.max_position_embeddings, c.num_attention_heads,
            c.hidden_size // c.num_attention_heads, dtype,
            kv_dtype=kv_dtype)

    def gen_paged_cache(self, batch_size, dtype="float32", max_len=None,
                        page_size=64, kv_dtype=None):
        """Preallocated paged KV cache (``serving.cache.PagedKVCache``)
        with a DENSE identity page table — slot ``i`` owns its own page
        run, so model-level use needs no allocator (the serving engine
        builds the pooled/shared layout through ``serving.pages``).
        ``model(x, cache=paged)`` decodes through the page-gather
        attention path; capacity matches :meth:`gen_cache`.
        ``kv_dtype="int8"`` selects the quantized pool (see
        :meth:`gen_cache`)."""
        from ..serving.cache import PagedKVCache
        c = self.config
        return PagedKVCache.create_dense(
            batch_size, c.num_hidden_layers,
            max_len or c.max_position_embeddings, c.num_attention_heads,
            c.hidden_size // c.num_attention_heads,
            min(int(page_size), int(max_len or c.max_position_embeddings)),
            dtype, kv_dtype=kv_dtype)

    def gen_legacy_concat_cache(self, batch_size, dtype="float32"):
        """COMPAT SHIM — the pre-serving concat-grown cache: the K/V
        arrays grow by one token per step, so the cache SHAPE changes
        every call and any jit around the decode retraces and recompiles
        per generated token.  Kept only for exported-artifact parity and
        old callers; everything new uses :meth:`gen_cache` (static
        slotted) or :meth:`generate`."""
        c = self.config
        empty = ops.zeros(
            [batch_size, 0, c.num_attention_heads,
             c.hidden_size // c.num_attention_heads], dtype)
        return [(empty, empty) for _ in range(c.num_hidden_layers)]

    def generate(self, input_ids, max_new_tokens=20, temperature=1.0,
                 top_k=0, top_p=1.0, eos_token_id=None, seed=0,
                 num_slots=None, max_len=None, greedy=None, **engine_kw):
        """Generate continuations through the serving engine (static
        paged cache + continuous-batching decode — the decode step
        compiles once, not once per token).

        ``input_ids``: (batch, prompt_len) int array (or a list of 1-D
        prompts of different lengths).  Returns a list of 1-D int32
        numpy arrays of generated tokens (prompt excluded).
        ``greedy=True`` is shorthand for temperature 0.  Extra keyword
        arguments reach the engine geometry (``serving.engine_for``):
        ``tp=N`` decodes tensor-parallel over N chips (ISSUE 12),
        ``kv_dtype="int8"`` / ``spec_k=k`` select the quantized /
        speculative modes."""
        from ..serving import generate as _generate
        if greedy:
            temperature = 0.0
        return _generate(self, input_ids, max_new_tokens=max_new_tokens,
                         temperature=temperature, top_k=top_k, top_p=top_p,
                         eos_token_id=eos_token_id, seed=seed,
                         num_slots=num_slots, max_len=max_len,
                         **engine_kw)


class GPTPretrainingCriterion(Layer):
    """Shifted-causal-LM loss (reference analogue: the fleet GPT model's
    criterion)."""

    _scope = _scopes.LOSS

    def forward(self, logits, labels, loss_mask=None):
        # shift via the LABELS, not the logits: slicing logits[:, :-1, :]
        # copies the whole (B, S, V) array (~1GB of HBM traffic at GPT-2
        # bench shapes); rolling the small int labels and masking position
        # S-1 with ignore_index computes the same loss without it
        b, s = labels.shape[0], labels.shape[1]
        targets = ops.concat(
            [labels[:, 1:], ops.full([b, 1], -100, labels.dtype)], axis=1)
        loss = F.cross_entropy(logits, targets, reduction="none",
                               ignore_index=-100)
        denom = float(s - 1) / float(s)  # mean over the S-1 real positions
        if loss_mask is not None:
            mask = ops.concat(
                [loss_mask[:, 1:], ops.zeros([b, 1], loss_mask.dtype)],
                axis=1)
            return ops.sum(loss * mask) / ops.maximum(
                ops.sum(mask), ops.to_tensor(1.0))
        return ops.mean(loss) / denom


def gpt2_345m():
    return GPTForCausalLM(GPTConfig.gpt2_medium())
