"""The pass at which a token leaves a looped model in expectation, ``sum_t t *
mean p_t`` with the passes counted from 1: the program's own
``engine.sown_stats("loop")["exit_mass"]`` of each traced step, as the runner
sampled it, averaged. About 1.875 at a seeded gate of four passes (p = .5,
.25, .125, .125); 1 or 4 says the gate is dead. It describes the model's
state, not the program's speed: the manifest has to give every metric a
direction, so it says ``higher``, but the number is read beside
``train_tok_s`` and never judged. A program that sows no such family reports
nothing."""


def read(run):
    samples = run.get("loop_exit_mass_samples")
    if not samples:
        return None
    passes = [sum((t + 1) * p for t, p in enumerate(mass)) for mass in samples]
    return sum(passes) / len(passes)
