"""A number the runner counted: `args.key` names it."""


def read(args, env):
    value = env.samples.get(args["key"])
    return None if value is None else float(value)
