"""A kernel's operations and bytes in a file beside its metric: what a later
PR adds for a new kernel (fixture: ``tiny.add_new_family`` copies it into
``<copy>/benchmark/layer_metrics/``)."""


def router_step_cost(sizes, sequences, bytes_per_el=2):
    """A router's logits over ``sizes.experts`` experts, forward and
    backward, all layers, one step."""
    tokens = sequences * sizes.seq
    return {"flops": sizes.layers * 3 * 2 * tokens * sizes.hidden
            * sizes.experts,
            "bytes": sizes.layers * 3 * bytes_per_el * (
                tokens * sizes.hidden + sizes.hidden * sizes.experts
                + tokens * sizes.experts)}
