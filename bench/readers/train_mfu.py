"""Share of the chip's peak bf16 rate, in %, that the window's training
rate came to: operations each token needs (as the configuration's family
module counts them, ``bench.program.family``; no recomputation) times
tokens per second."""
from bench import program


def read(run, config, peaks):
    rate = run.metrics.get("train_tokens_per_s")
    if not rate:
        return None
    per_token = program.family(config).train_flops_per_token(
        config, config["train"]["seq"])
    return 100.0 * per_token * rate / peaks["bf16_flops_per_s"]
