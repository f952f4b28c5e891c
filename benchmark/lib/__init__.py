"""The benchmark's own code: the yardstick that later PRs cannot change."""
