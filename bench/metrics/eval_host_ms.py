"""eval_host_ms (ms; evaluation service): mean host time per evaluate batch
outside the fetch: planning and grouping (program span ``eval_plan``, one
a batch), the executable lookup and launch of every group
(``eval_dispatch``), and slicing the members' results (``eval_assemble``)."""
from bench import program_spans


def read(run):
    return program_spans.mean_ms(
        run, ["eval_plan", "eval_dispatch", "eval_assemble"], "eval_plan")
