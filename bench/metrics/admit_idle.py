"""Share of the traced span in which the chip is idle while the host is
in admission (``serve.admit``) outside its child spans: scheduling, the
extend dispatch, the first-token sync, the prefix registry."""
import program_spans


def read(run):
    return program_spans.idle_share(run, "admit")
