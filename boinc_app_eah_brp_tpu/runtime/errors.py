"""Exit/error codes matching the reference (``demod_binary.h:24-73``).

The science codes (1-5) keep their exact values so BOINC server-side error
triage keeps working. The 1000/2000 ranges were CUDA/OpenCL-specific; the
TPU device path reports its failures in an analogous 3000 range.
"""

RADPUL_EMEM = 1
RADPUL_EFILE = 2
RADPUL_EIO = 3
RADPUL_EVAL = 4
RADPUL_EMISC = 5

# TPU device-path errors (new range, mirroring the CUDA/OpenCL blocks)
RADPUL_TPU_DEVICE_FIND = 3001
RADPUL_TPU_COMPILE = 3002
RADPUL_TPU_EXEC = 3003
RADPUL_TPU_MEM = 3004

# Watchdog hard exit: the supervisor thread detected an unrecoverable
# stall (a wedged dispatch, a stuck collective, blocked lease IO) and the
# cooperative abort did not unwedge it.  This is the analogue of
# ``boinc_temporary_exit`` (erp_boinc_wrapper.cpp:560-570): the process is
# healthy enough to be re-run, so a supervisor (tools/supervise.py, or the
# BOINC client in the reference) should restart it from the last committed
# checkpoint rather than treat the workunit as failed.
RADPUL_TEMPORARY_EXIT = 99


class RadpulError(RuntimeError):
    def __init__(self, code: int, message: str):
        super().__init__(message)
        self.code = code
