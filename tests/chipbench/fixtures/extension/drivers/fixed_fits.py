"""A fixed number of whole fits, however long the window: a driver added as
a file of its own.  Traffic keys: those of ``fit`` and ``fits``."""
from chipbench.drivers import fit


class Driver(fit.Driver):

    def window(self, seconds: float) -> None:
        for _ in range(int(self.traffic["fits"])):
            model = self.fit_once()
        self.means_t = model.index.means_t


control = fit.control
