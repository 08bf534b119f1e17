"""The chip benchmark's yardstick: what a cell is made of (``spec``),
the architectures it serves (``arch/<name>.py``: the program's
configuration, the weight layout, the reference's forward pass and the
costs of the work), the traffic it sends (``traffic_gen``), the weight
draw (``weights``), the plain reference (``reference``) and the
comparison that decides ``correct`` (``check``), the window it times
(``serve_loop``), the trace reduction (``trace_reduce``), and the
arithmetic the metric readers share (``reading``, ``peaks``).  Nothing
here imports the program at module level: ``serve_loop`` reaches the
engine only when a run asks for it.
"""
