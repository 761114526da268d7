"""Device: `memory_stats()["peak_bytes_in_use"]` in the chip holder after
the window, the fullest chip."""


def read(obs):
    peaks = [r["device"]["memory_peak_bytes"] for r in obs.get("replicas", [])]
    return max(peaks) / 2 ** 30 if peaks and max(peaks) else None
