"""Peak bytes in use on the fullest device, as the backend reports it."""


def read(args, env):
    if not env.memory_peak_bytes:
        return None
    return env.memory_peak_bytes / 1e9
