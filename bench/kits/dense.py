"""The kit of the dense decoder: pre-norm GQA attention and one MLP of
``d_ff`` hidden rows per layer, gated or plain (``weights.py``,
``reference.py``, ``flops.py``).

A kit is what the harness knows about one kind of layer.  A configuration
file names its kit under ``"kit"`` (``dense`` where it names none), and
``spec.kit`` loads ``bench/kits/<kit>.py`` by that name, so a configuration
whose layer is of another kind comes in as new files: its kit, its
configuration, its limits.  ``run.py`` reads nothing of a layer but through
these four names, which every kit exposes:

``Generator(model, weights)``
    From the configuration's ``model`` and ``weights`` blocks.
    ``program_params(seed, expected)`` makes the program's whole parameter
    tree on the device in one jitted call and raises where ``expected``
    (the program's own shapes) differs, leaf for leaf; what else it offers
    is for its ``Reference``, which rebuilds the served values from the
    seed and never takes them from the program.

``Reference(model, gen, bucket, fmt)``
    The float32 reference at ``highest`` precision, layer by layer, each
    request padded to a multiple of ``bucket`` positions.
    ``served(seed, prompts, outs, tokens=None)`` teacher-forces each prompt
    with its served tokens and gives, per served token, (best logit, the
    token's logit, argmax); ``tokens`` replaces the tokens whose logit is
    read.  ``fmt`` "int8" or "fp8" computes it with every weight matrix in
    that format (the control); ``times`` holds its phases' seconds.

``decode_mlp_rows(model, sparse_cfg, slots)``
    What one decode token's MLP computes at this batch, in whatever form
    the kit's ``decode_token_flops`` reads: ``run.py`` passes it through.

``decode_token_flops(model, ctx, mlp_rows)``
    Operations one decode token needs at live context ``ctx`` (its own
    position included), a multiply-add counting two.

A kit for a mixture-of-experts layer keeps the same four names with its
own layout: a layer's weights may carry an expert axis, a router and
shared experts, which its ``Generator`` lays out as the program does and
its ``Reference`` routes through; and a token's MLP work is the rows that
its ``top_k`` routed experts compute (each expert's selected row groups on
the sparse path, its whole width on the dense one) plus the shared
experts' rows and the router's projection, not one ``d_ff``.
"""
from bench import flops, reference, weights

Generator = weights.Generator
Reference = reference.Reference
decode_token_flops = flops.decode_token_flops


def decode_mlp_rows(m: dict, sparse_cfg, slots: int) -> int:
    """Hidden rows one decode token's MLP computes: the capacity's row
    groups on the sparse path; every row where sparsity is off or the batch
    is over ``sparse_max_batch`` (``layers/mlp.py`` runs the dense MLP)."""
    if not sparse_cfg.enabled or slots > sparse_cfg.sparse_max_batch:
        return m["d_ff"]
    return sparse_cfg.capacity(m["d_ff"]) * sparse_cfg.group_size
