"""Start of the process to the first timed request: loading the store,
booting the server (mirror rebuild included), starting the generators and
warming up; on a checkout's first run, compilation too."""


def read(ctx):
    return ctx.setup_s
