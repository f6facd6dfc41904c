"""Mean prefill time of the generation tasks counted in the window: from
the prefill's dispatch to its first token ready on the host's clock, as
``launch.serve.generate`` stamps them (``prefill_dispatch``,
``first_token``). A program that does not stamp them reads nothing."""
from harness.readers import counted
from harness.serve_stamps import mean_ms


def read(run):
    return mean_ms(counted(run, "generate_loop"), "prefill_dispatch",
                   "first_token")
