"""The dense causal decoder of ``progen-s``, ``foldscore-s`` and
``foldscore-m``: pre-norm RMSNorm layers with GQA attention (half-rotation
RoPE) and a SwiGLU MLP. The weights and the reference are
``bench/reference.py``'s, the work counts ``bench/flops.py``'s."""

from bench import flops, reference

_PARAMS = {"generator": reference.generator_params,
           "scorer": reference.scorer_params}


def params(key, m, kind):
    return _PARAMS[kind](key, m)


token_logprobs = reference.token_logprobs
fold_metrics = reference.fold_metrics
generator_flops = flops.generator_flops
scorer_flops = flops.scorer_flops
scorer_call = flops.scorer_call


def step_counts(m, kv_lens):
    """The step's one counted kernel: the Pallas paged decode attention
    (``readers.PAGED_KERNEL``)."""
    return {"paged_decode": flops.paged_decode_step(m, kv_lens)}
