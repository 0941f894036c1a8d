"""Device: ``memory_stats()['peak_bytes_in_use']`` on the fullest chip,
read when the window has closed (set-up's peaks included)."""

META = dict(source="program_counter", layer="device", unit="GB", moves="tokens_per_s_per_chip")


def read(sources):
    return sources["peak_bytes"] / 1e9 if sources["peak_bytes"] else None
