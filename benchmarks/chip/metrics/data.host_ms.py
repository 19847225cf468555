"""Input pipeline: host milliseconds per step spent drawing the batch and
placing it on the devices (the ``data`` span), over the measured window."""


def reduce(run):
    if not run.host_data_s:
        return None
    return 1e3 * sum(run.host_data_s) / len(run.host_data_s)
