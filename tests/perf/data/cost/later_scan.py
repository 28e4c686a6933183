"""Test only: the cost function a later PR brings for a kernel of its own,
here a recurrent-state decode kernel whose bytes depend on what the step
held and not on the context length. Per row, head and layer the algorithm
reads and writes one float32 state of ``head_dim`` x ``head_dim`` and does
four operations on each of its elements (decay and update, then the
query's product). The step's ``pst.step_info`` reaches it whole: ``rows``,
or ``state_slots`` where the program writes that field."""


def cost(step: dict, hf: dict, cfg) -> dict:
    rows = step.get("state_slots") or step.get("rows")
    if not rows:
        return None
    heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    elements = rows * heads * head_dim * head_dim * hf["num_hidden_layers"]
    return {"flops": 4.0 * elements, "bytes": 2 * 4 * elements,
            "peak": "bf16_flops_per_s"}
