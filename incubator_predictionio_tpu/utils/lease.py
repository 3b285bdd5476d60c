"""Exit cleanly while holding the accelerator.

A chip belongs to one process at a time. On a local chip that is
libtpu's lock file: while one process holds the chip, a second one that
initializes the TPU backend FAILS at once with an error naming the lock
and its holder — it does not queue behind it. The lock goes when the
holder exits, however it exits.

:func:`install_sigterm_exit` converts SIGTERM into ``SystemExit`` so
`timeout`, supervisors and Ctrl-style termination tear a chip-holding
process down through the interpreter instead of around it: ``finally``
blocks and ``atexit`` run (servers close their sockets, stores flush,
the PJRT client is destroyed), where SIGTERM's default action is
immediate death. The handler runs between bytecodes: a dispatch blocked
inside the PJRT client returns first, then the exit proceeds. Where an
asyncio event loop runs on the main thread (the CLI's server verbs) the
``SystemExit`` is raised as a callback of that loop, not in the middle
of one.

The CLI installs it at entry (cli/main.py); chip-side scripts install it
once ``jax.devices()`` has returned.
"""

from __future__ import annotations

import signal
import sys
import threading


def install_sigterm_exit(code: int = 143) -> bool:
    """Install a SIGTERM → ``SystemExit(code)`` handler (main thread
    only; signal handlers cannot be installed elsewhere). Returns True
    when installed. Idempotent; never raises."""
    if threading.current_thread() is not threading.main_thread():
        return False
    try:
        def _raise():
            # raising (not os._exit) unwinds through finally blocks and
            # atexit
            raise SystemExit(code)

        def _exit(_signum, _frame):
            asyncio = sys.modules.get("asyncio")
            loop = asyncio._get_running_loop() if asyncio else None
            if loop is None:
                return _raise()
            # raised here it would land between two bytecodes of the
            # loop's own code, where a callback can have left the ready
            # queue and not yet run: a task whose wake-up is lost that
            # way never ends, and asyncio.run's clean-up awaits it for
            # ever (`pio storageserver` hung after SIGTERM in ~5% of
            # stops under load). As a callback of its own it leaves
            # every other callback where the clean-up finds it.
            loop.call_soon_threadsafe(_raise)

        signal.signal(signal.SIGTERM, _exit)
        return True
    except (ValueError, OSError):  # non-main interpreter contexts
        return False


def _selftest() -> None:  # pragma: no cover - manual aid
    install_sigterm_exit()
    signal.raise_signal(signal.SIGTERM)


if __name__ == "__main__":  # pragma: no cover
    _selftest()
    sys.exit(1)  # unreachable if the handler worked
