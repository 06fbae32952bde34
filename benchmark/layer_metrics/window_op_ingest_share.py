"""Share of the measured window inside the window operator's batch
entry: the harness's wrapper around the entry the configuration names
(``expect.ingest_entry``)."""


def read(run):
    if not run["end"]["ingest_calls"]:
        return None
    return 100.0 * run["end"]["ingest_s"] / run["window_s"]
