"""The busiest held expert's tokens over the mean held expert's, a step and a
routed layer, averaged over the run's steps and layers: the program's counters
``trainer_moe_expert_tokens_max{layer}`` (a step's maximum, summed over steps)
over ``trainer_moe_held_assignments_total{layer}`` divided by the experts held.
1 is a perfectly even load; the grouped product's tiles are padded per expert,
so an uneven load costs tiles.  A program without the counters reads nothing.
"""

META = {
    "name": "moe_expert_load_max_over_mean",
    "unit": "ratio",
    "better": "lower",
    "source": "program_counter",
    "layer": "sequence tower",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    counters = ctx.get('counters') or {}
    held = ctx['cfg'].get('num_experts')
    ratios = []
    for name, most in counters.items():
        if name.startswith('trainer_moe_expert_tokens_max'):
            total = counters.get(name.replace(
                'trainer_moe_expert_tokens_max', 'trainer_moe_held_assignments_total'))
            if total and held:
                ratios.append(most / (total / held))
    return sum(ratios) / len(ratios) if ratios else None
