"""How evenly the router spread the work: the program's own gauge
``ds_moe_expert_load_max_over_mean`` (busiest expert's assignments over the
mean expert's, counts summed over the MoE layers), as the runner sampled it
after each traced step, averaged. 1.0 is an even split; a program that
publishes no such gauge reports nothing."""


def read(run):
    samples = run.get("moe_load_samples")
    return sum(samples) / len(samples) if samples else None
