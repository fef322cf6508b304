"""One module per kind of traffic, named by a traffic file's ``driver``.

A driver exposes ``build_tuner(traffic)``, ``Program(config, traffic,
seed, tuner, scale)`` (set-up; ``request(i)`` answers one request,
``release()`` frees the program and returns what the check needs),
``check(inputs, answers, traffic)`` (the numbers compared, per answer) and
``control(inputs, config, traffic, seed, count)`` (the reference at the
lower precision, answering in the program's place).
"""
