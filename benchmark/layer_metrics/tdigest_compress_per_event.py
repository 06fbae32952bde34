"""Digests the t-digest's ingest compressed (a buffer of 512 values
full and one more arriving) per event taken in over the measured
window, from the aggregate's own count (read from the device at the
window's two marks).  Reckoned for Zipf 0.99 over 10M keys at 16,384
events a slide: about 0.0037."""

import tdigest


def read(run):
    return tdigest.compress_per_event()
