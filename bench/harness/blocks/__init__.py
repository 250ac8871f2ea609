"""Decoder blocks: one module per kind, named by a configuration file's
``"block"`` key and loaded from ``bench/harness/blocks/<block>.py`` by
``cli.find_cell``. A new architecture enters the benchmark as one new file
here and edits none: everything that depends on the block comes from its
module, and nothing outside it names a block's keys, weights or layers.

A block module provides:

- ``Dims``: a frozen dataclass of the block's keys of the published config.
  ``Dims.from_config(model)`` reads the configuration's ``model`` strictly:
  a key it does not read, or one it lacks, is a ``cli.BenchError`` that
  names it. The shared code reads ``num_hidden_layers`` and ``vocab_size``;
  ``dims.linears()`` lists ``(name, d_in, d_out)`` of one block's linears
  in the stored orientation, the floats that are served quantized.
- ``block_f32(key, dims) -> dict``: block ``key``'s float32 weights, the
  linears stored ``(d_in, d_out)``; the reference makes them again, block
  by block, from the same key.
- ``outer_f32(key, dims, pair_offset) -> dict``: the float32 weights
  outside the blocks under the program's names, ``embed`` (vocab, d) and
  ``lm_head`` (d, vocab) among them; ``pair_offset`` is
  ``weights.Recipe.head_pair_offset``.
- ``served_block(w, quantize) -> dict``: one block's subtree of the
  program's parameter tree from ``block_f32``'s floats, ``quantize``
  packing a stored linear.
- ``program_config(base, dims)``: the program's ``ModelConfig``, ``base``
  (the repo's config of the file's ``arch``) at the file's sizes.
- ``layer(w, x, dims) -> x``: the plain float32 reference of one block over
  one sequence ``x`` (T, d), causal from position 0, ``w`` the block's
  floats with its linears quantized and dequantized; ``jax.numpy`` only,
  at the caller's matmul precision.
- ``final_norm(x, outer, dims)``: the reference's norm before the head.
- ``decode_flop(dims, tokens, context) -> int``: model operations of
  ``tokens`` decode tokens whose live contexts sum to ``context``, two per
  multiply-add.
- ``decode_attention(dims) -> (layers, heads, kv_heads, head_dim)``: the
  layers whose decode step runs ``flash_decode`` over the paged KV pool,
  and their heads; a pool page holds (page size, kv_heads, head_dim) of
  one layer.
"""
