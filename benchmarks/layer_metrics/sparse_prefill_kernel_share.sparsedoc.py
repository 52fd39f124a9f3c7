"""``S`` blocks that attended through the masked K/V-tiled flash kernel
(``mx_attention_tiled_masked``: one pass over the expanded form, masked
to the indexer's selection) over all the ``S`` blocks of the prefills in
the traced window, in percent: the ``sparse_kernel_layers`` and
``sparse_layers`` arguments the engine writes on every ``engine.prefill``
span of a model with ``S`` blocks.  None where the spans carry neither (a
program without the kernel's route)."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.span_args_ratio(trace, "engine.prefill",
                                         "sparse_kernel_layers",
                                         "sparse_layers")
