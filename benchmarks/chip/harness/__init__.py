"""The chip benchmark's yardstick: what a cell is made of (``spec``),
the traffic it sends (``traffic_gen``), the weights it serves
(``weights``), the plain reference (``reference``) and the comparison
that decides ``correct`` (``check``), the window it times
(``serve_loop``), the trace reduction (``trace_reduce``), and the
arithmetic the metric readers share (``reading``, ``costs``, ``peaks``).
Nothing here imports the program at module level: ``serve_loop``
reaches the engine only when a run asks for it.
"""
