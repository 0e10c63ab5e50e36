"""One module per architecture the benchmark runs.

A configuration file names its module by path (``"arch_module":
"chipbench/archs/<arch>.py"``), and ``spec.load_module`` loads it. A new
architecture is a new file here; the files already here stay as they are.
Each module provides:

* ``FIELDS``: the ``ModelConfig`` fields the module reads, with their
  defaults where the configuration's ``program`` does not set them
  (``config.dims`` adds them to every architecture's ``rms_eps``,
  ``tie_embeddings``, ``n_layers``, ``d_model`` and ``vocab_size``);
* ``REHEARSAL``: the ``ModelConfig`` overrides of the CPU rehearsal
  (``rehearsal.py``), widths cut until the CPU holds a cell;
* ``layout(d)``: ``{name: (shape, std)}`` of the layer stack's leaves, as
  ``weights.layout`` takes them (``std`` 0 is zeros, ``"ones"`` ones);
* ``groups(n_steps, refresh_interval)``: the steps of one block as the
  reference replays them, ``[(refresh step, [reuse steps that read what
  it kept])]``;
* ``block_hidden(ref, ctx, steps, refresh_s, reuse_ss, bstart, total_len,
  quant)``: the block's final hidden rows ``[Sb, D]`` (before the final
  norm) at ``refresh_s`` and at each of ``reuse_ss``, replayed by the plain
  reference ``ref`` (``reference.Reference``: weights, serve settings,
  embedding) from the finished context ``ctx``; ``quant`` is the control's
  mode for ``reference.lin``;
* ``matmul_flops_per_token(d)`` and ``attention_flops(d, queries, keys)``,
  for ``work.py``.
"""
