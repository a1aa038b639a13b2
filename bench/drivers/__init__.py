"""Drivers turn a traffic mix into calls of the program.

``bench/drivers/<name>.py`` defines ``Driver(config, traffic, seed,
seconds, devices)`` with:

* ``warm()``: set-up that the window needs, every shape compiled;
* ``call(i)``: the i-th timed call; returns the units it answered once the
  answer is on the host;
* ``after(i)``: bookkeeping after call i, outside its timing;
* ``counters()``: the program's counters over the window;
* ``release()``: frees the program's state before the reference runs;
* ``check(control=False)``: ``{"checks": [(name, value, limit)],
  "failed": calls answered wrongly}``; with ``control`` the comparison is
  made of the control's answers instead (see ``bench/control.py``).
"""
