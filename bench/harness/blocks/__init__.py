"""Decoder blocks: one module per kind, named by a configuration file's
``"block"`` key and loaded from ``bench/harness/blocks/<block>.py`` by
``cli.find_cell``. A new architecture enters the benchmark as one new file
here and edits none: everything that depends on the block comes from its
module, and nothing outside it names a block's keys, weights or layers.
Layers may differ from one another (windowed and full attention, dense and
expert MLPs): the reference tells each layer its index, and the operation
counts take each decoded row's own context.

A block module provides:

- ``Dims``: a frozen dataclass of the block's keys of the published config.
  ``Dims.from_config(model)`` reads the configuration's ``model`` strictly:
  a key it does not read, or one it lacks, is a ``cli.BenchError`` that
  names it. The shared code reads ``num_hidden_layers`` and ``vocab_size``;
  ``dims.linears()`` lists one block's linears in the stored orientation,
  the floats that are served quantized: ``(name, d_in, d_out)`` for one
  linear stored (d_in, d_out), ``(name, d_in, d_out, n)`` for ``n`` of them
  stacked (n, d_in, d_out), as a layer's experts are.
- ``block_f32(key, dims) -> dict``: block ``key``'s float32 weights, the
  linears stored as ``dims.linears()`` lists them; the reference makes them
  again, block by block, from the same key.
- ``outer_f32(key, dims, pair_offset) -> dict``: the float32 weights
  outside the blocks under the program's names, ``embed`` (vocab, d) and
  ``lm_head`` (d, vocab) among them; ``pair_offset`` is
  ``weights.Recipe.head_pair_offset``.
- ``served_block(w, quantize) -> dict``: one block's subtree of the
  program's parameter tree from ``block_f32``'s floats, ``quantize``
  (``weights.quantize``) packing a stored linear: a (d_in, d_out) one into
  a ``QTensor`` of shape (d_out, d_in), a stacked (n, d_in, d_out) one into
  the stacked ``QTensor`` that ``repro.models.layers.expert_apply`` reads
  (children leading with n, the aux shape one slice's (d_out, d_in)).
- ``program_config(base, dims)``: the program's ``ModelConfig``, ``base``
  (the repo's config of the file's ``arch``) at the file's sizes.
- ``layer(w, x, dims, i) -> x``: the plain float32 reference of block ``i``
  (a Python int, 0 first) over one sequence ``x`` (T, d), causal from
  position 0, ``w`` the block's floats with its linears quantized and
  dequantized; ``jax.numpy`` only, at the caller's matmul precision.
- ``final_norm(x, outer, dims)``: the reference's norm before the head.
- ``decode_flop(dims, contexts) -> int``: model operations of one decode
  token for each live context (tokens) in ``contexts``, two per
  multiply-add.
- ``decode_attention(dims) -> tuple``: one ``(heads, kv_heads, head_dim,
  window)`` for each layer whose decode step runs ``flash_decode`` over the
  paged KV pool, ``window`` the keys a query reads at most (``None`` for
  full attention). There is one pool, so every such layer has the same
  page (page size, kv_heads, head_dim).
"""
