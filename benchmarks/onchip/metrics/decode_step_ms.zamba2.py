"""Mean decode step of the generation tasks counted in the window: from the
first token ready to the last token ready, over the decode steps, as
``launch.serve.generate`` stamps and counts them (``first_token``,
``last_token``, ``decode_steps``). A program that does not stamp them
reads nothing."""
from harness.readers import counted
from harness.serve_stamps import mean_ms


def read(run):
    return mean_ms(counted(run, "generate_loop"), "first_token",
                   "last_token", per="decode_steps")
