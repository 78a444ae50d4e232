"""Architecture modules: one file per architecture, ``arch/<name>.py``.

A configuration file names its architecture with the top-level key
``"arch"``. The harness loads ``arch/<arch>.py`` by that name
(``harness.load_arch``, from ``load_cell``), and from then on reaches
the weights, the reference and every count that depends on the model's
shape only through that module: ``run.arch``, with ``run.model =
run.arch.model(cfg)``. A module defines:

``model(cfg) -> dict``
    The sizes that the functions below read, from the configuration
    file ``cfg``. It holds ``vocab`` (the traffic draws its token ids
    below it, the check refuses tokens outside it) and ``n_layers``.
``build_params(seed, model)``
    The served parameter tree, made on the device from the seed, in the
    layout ``ServeEngine`` consumes.
``served_gaps(seed, model, requests, control=False)``
    The plain float32 reference, importing nothing of the program:
    ``(gaps, control_gaps)`` for ``(prompt, served)`` token pairs, as
    ``reference.served_gaps`` sets out; ``harness.check`` takes the
    widest.
``model_flops(model, contexts) -> float``
    Forward FLOPs of one token per entry of ``contexts`` (the keys it
    attends over); read by ``metrics/step_mfu.py``.
``projection_dims(model) -> [(layers, [(d_in, d_out), ...]), ...]``
    The weight matmuls that run through ``quant_matmul`` in one step,
    grouped by the layers that share them: how many layers, and each
    matmul's shape; read by ``metrics/quant_matmul_roofline.py``.
``paged_attention(model, contexts) -> (flops, bytes)``
    The least work and traffic of decode attention over the paged KV
    pool, one entry of ``contexts`` per active row of one step, summed
    over every layer; read by ``metrics/paged_attention_roofline.py``.

Pieces that any architecture reuses live beside the harness:
``weights.py`` (takum8 words of a Gaussian, the run's root key, the
embeddings), ``reference.py`` (takum8 decode, int4 rounding for the
control, RMSNorm, RoPE, blockwise causal attention, the unembedding
head, the layer-by-layer forward and the gaps) and ``costs.py`` (one
quantised matmul's work and bytes, the peaks, the roofline time).
"""
