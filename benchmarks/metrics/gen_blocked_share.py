"""Share of the traffic's time the generator's writers waited in
``drain()``, averaged over the sockets. Near 100 % in a flood: the server
sets the pace; near 0: the generator does. Layer: socket edge."""


def read(ctx):
    return ctx.client.get("gen_blocked_share")
