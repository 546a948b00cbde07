"""Share of the traced span in which the chip is idle while the host is
in the decode step (``serve.step``) outside its child spans: argument
upload, the decode dispatch, token readback, record and retire."""
import program_spans


def read(run):
    return program_spans.idle_share(run, "step")
